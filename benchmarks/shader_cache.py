"""§3.4 GPU analogue: XLA executable ("shader") caching — compile time vs
deserialize-from-disk time per layer, the cold-start stage the compile cache
removes.

The executable directory is shared across runs and models (see
``core/compile_cache.py``), so the compile arm uses a cache with no disk
layer and JAX's persistent cache switched off: every executable really
compiles, whatever earlier runs left behind. The deserialize arm reads the
shared directory, filled first where this host has not compiled these
programs before."""
from __future__ import annotations

import contextlib
import tempfile

import jax
from jax.experimental.compilation_cache import compilation_cache

from benchmarks.common import build_engine, csv_line


@contextlib.contextmanager
def _no_persistent_cache():
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", old)
        compilation_cache.reset_cache()


def run(print_csv=True, model="mobilenet"):
    from repro.core.compile_cache import CompileCache
    from repro.core.engine import ColdEngine
    from repro.models.cnn import build_cnn

    with tempfile.TemporaryDirectory() as store:
        eng, x = build_engine(model, store=store)

        def engine_on(cache):
            layers, x2 = build_cnn(model, image=40, width=0.6)
            e = ColdEngine(layers, store)
            e.compile_cache = cache
            e.plan, e.profiles, e._input_example = eng.plan, eng.profiles, x2
            e.make_runtime(n_little=2)
            return dict(cache.stats)

        # compile arm: no disk layer, no persistent cache -> all compile
        with _no_persistent_cache():
            s1 = engine_on(CompileCache(None))
        # deserialize arm: fill the shared directory, then read it back
        engine_on(CompileCache(eng.compile_cache.root))
        s2 = engine_on(CompileCache(eng.compile_cache.root))
    if print_csv:
        print(csv_line("shader_cache/compile_total", s1["compile_s"],
                       f"misses={s1['misses']}"))
        print(csv_line("shader_cache/deserialize_total", s2["deserialize_s"],
                       f"disk_hits={s2['disk_hits']};"
                       f"speedup={s1['compile_s']/max(s2['deserialize_s'],1e-9):.1f}x"))
    return s1, s2


if __name__ == "__main__":
    run()
