"""Executable ("shader") cache — §3.4 adapted to XLA.

On GPU the paper caches compiled SPIR-V shaders to skip shader compilation in
cold inference. The XLA analogue is jit compilation: each (kernel, shape)
pair costs a lower+compile on first use. We cache serialized compiled
executables on disk via ``jax.experimental.serialize_executable`` and restore
them on cold start, turning the compile stage into a (much cheaper) disk
read — exactly the shader-cache trade.

Keys:

  * in memory by (kernel, *shape-class*, example shapes, jax/jaxlib
    version, device): the L byte-identical decoder blocks of an LLM graph
    share ONE compiled executable instead of compiling L times
    (``registry.shape_class_key``), and a hit costs no tracing at all;
  * on disk by that key plus a hash of the lowered program text: the
    directory is shared by every model, store and checkout, so an entry is
    named by the code it was compiled from — an edited kernel body, or
    another model's stateless layer with the same name and shapes, lowers
    to other text and misses instead of loading a stale executable;
  * the jax/jaxlib version, the backend and ``device_kind`` are in both
    keys, so entries from another runtime, a CPU run or another TPU
    generation miss cleanly instead of relying on a deserialize exception;
  * examples may be real arrays or ``jax.ShapeDtypeStruct`` avatars — the
    cache only lowers, so no weight bytes are needed to compile.

Placement: every compile cache of the program lives under
:func:`cache_root` — ``$JAX_COMPILATION_CACHE_DIR`` when set, else the fixed
``.jax_cache/`` directory of the checkout. JAX's own persistent compilation
cache is that directory (:func:`setup_compile_cache`, called once by each
entry point); these executables go to its ``executables/`` subdirectory.
"""
from __future__ import annotations

import functools
import hashlib
import os
import pickle
import time
from pathlib import Path
from typing import Callable, Dict, Optional, Tuple

import jax

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/core/compile_cache.py -> the checkout root
_CHECKOUT = Path(__file__).resolve().parents[3]


def cache_root() -> Path:
    """The one directory every compile cache lives under."""
    env = os.environ.get(ENV_CACHE_DIR)
    return Path(env) if env else _CHECKOUT / ".jax_cache"


def executable_cache_dir() -> Path:
    return cache_root() / "executables"


def setup_compile_cache() -> Path:
    """Entry-point setup: JAX's persistent compilation cache at
    :func:`cache_root`. When ``$JAX_COMPILATION_CACHE_DIR`` is set JAX
    already reads it, and no other directory is set here."""
    root = cache_root()
    if not os.environ.get(ENV_CACHE_DIR):
        jax.config.update("jax_compilation_cache_dir", str(root))
    return root


@functools.lru_cache(maxsize=1)
def _version_tag() -> str:
    """jax/jaxlib versions — constant per process, probed once. Also feeds
    ``profiler.host_fingerprint``."""
    try:
        import jaxlib

        jl = getattr(jaxlib, "__version__", "?")
    except Exception:  # pragma: no cover - jaxlib always ships with jax
        jl = "?"
    return f"{jax.__version__}/{jl}"


@functools.lru_cache(maxsize=1)
def device_tag() -> str:
    """Backend and device kind of the default device — what an executable
    (and a measured profile) is only valid for."""
    return f"{jax.default_backend()}/{jax.devices()[0].device_kind}"


def _key(kernel_name: str, ident: str, shapes: Tuple, version: str,
         device: str) -> str:
    h = hashlib.sha1(repr((kernel_name, ident, shapes, version,
                           device)).encode())
    return h.hexdigest()[:24]


class CompileCache:
    def __init__(self, root: Optional[Path] = None):
        self.root = Path(root) if root else None
        if self.root:
            self.root.mkdir(parents=True, exist_ok=True)
        self.mem: Dict[str, Callable] = {}
        self.stats = {"hits": 0, "misses": 0, "disk_hits": 0,
                      "compile_s": 0.0, "deserialize_s": 0.0,
                      "deserialize_failures": 0, "serialize_failures": 0}
        self.last_error: Optional[str] = None

    def get(self, kernel_name: str, spec, fn: Callable, w_example, x_example,
            *, shape_class: Optional[str] = None):
        """Returns a compiled callable for fn(w, x). ``shape_class`` is the
        sharing identity — all layers of one class get the same executable;
        without it the cache degrades to per-spec keying."""
        shapes = (
            tuple(sorted((k, tuple(v.shape), str(v.dtype))
                         for k, v in w_example.items())),
            (tuple(x_example.shape), str(x_example.dtype)),
        )
        ident = shape_class if shape_class is not None else spec.name
        key = _key(kernel_name, ident, shapes, _version_tag(), device_tag())
        if key in self.mem:
            self.stats["hits"] += 1
            return self.mem[key]
        # jax.jit is only built past the in-memory check, where a hit is
        # free; a disk hit pays the lowering, as its name needs the program
        t0 = time.perf_counter()
        lowered = jax.jit(fn).lower(w_example, x_example)
        path = None
        if self.root:
            code = hashlib.sha1(lowered.as_text().encode()).hexdigest()
            path = self.root / f"{_key(key, code, (), '', '')}.xla"
        if path and path.exists():
            try:
                from jax.experimental import serialize_executable as se

                with open(path, "rb") as f:
                    payload = pickle.load(f)
                compiled = se.deserialize_and_load(*payload)
                self.stats["deserialize_s"] += time.perf_counter() - t0
                self.stats["disk_hits"] += 1
                self.mem[key] = compiled
                return compiled
            except Exception as e:
                # unreadable entry: counted, then recompiled and rewritten
                self.stats["deserialize_failures"] += 1
                self.last_error = f"deserialize {path.name}: {e!r}"
        compiled = lowered.compile()
        self.stats["compile_s"] += time.perf_counter() - t0
        self.stats["misses"] += 1
        if path:
            try:
                from jax.experimental import serialize_executable as se

                payload = se.serialize(compiled)
                # write-then-rename: concurrent processes sharing the
                # directory never read a torn entry
                tmp = path.with_name(f"{path.name}.{os.getpid()}.tmp")
                with open(tmp, "wb") as f:
                    pickle.dump(payload, f)
                os.replace(tmp, path)
            except Exception as e:
                self.stats["serialize_failures"] += 1
                self.last_error = f"serialize {path.name}: {e!r}"
        self.mem[key] = compiled
        return compiled
