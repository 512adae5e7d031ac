"""Shape-class decision generation: profile/compile sharing + profile DB.

- canonical key properties (identical specs share, stateless never share);
- a graph of repeated identical blocks produces the SAME plan whether
  profiles are shared per shape-class or measured per layer (deterministic
  profiles);
- profile-DB round-trip: a second decide() performs zero Profiler.profile
  calls and reproduces the plan; host-fingerprint scoping;
- profiling writes no candidate cache entries into the model store;
- CompileCache keyed by (kernel, shape-class, jax version): one compile per
  class, stale-version entries miss cleanly, no jit built on hits.
"""
import numpy as np
import pytest

from repro.core.engine import ColdEngine
from repro.core.llm_graph import tiny_llm_graph
from repro.core.profiler import OpProfile, ProfileDB, SyntheticProfiler
from repro.core.registry import LayerSpec, shape_class_key

N_BLOCKS = 6


# ---------------------------------------------------------------------------
# the key itself
# ---------------------------------------------------------------------------
def test_identical_specs_share_key():
    a = LayerSpec("block000", "tblock", {"d": 4}, {"w": (8, 8)})
    b = LayerSpec("block007", "tblock", {"d": 4}, {"w": (8, 8)})
    assert shape_class_key(a) == shape_class_key(b)


def test_shape_and_config_and_input_feed_key():
    base = LayerSpec("l", "linear", {"in_features": 8, "out_features": 8},
                     {"w": (8, 8)})
    other_shape = LayerSpec("l", "linear",
                            {"in_features": 8, "out_features": 16},
                            {"w": (8, 16)})
    other_op = LayerSpec("l", "conv2d", {"in_features": 8, "out_features": 8},
                         {"w": (8, 8)})
    assert shape_class_key(base) != shape_class_key(other_shape)
    assert shape_class_key(base) != shape_class_key(other_op)
    assert (shape_class_key(base, input_shape=(1, 8), input_dtype="float32")
            != shape_class_key(base, input_shape=(2, 8),
                               input_dtype="float32"))


def test_stateless_layers_never_share():
    a = LayerSpec("relu1", "stateless")
    b = LayerSpec("relu2", "stateless")
    assert shape_class_key(a) != shape_class_key(b)


# ---------------------------------------------------------------------------
# engines over a graph with repeated identical blocks
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def llm_graph():
    return tiny_llm_graph(N_BLOCKS)


def _engine(graph, toks, store, *, share=True, db=None, profiler=None):
    eng = ColdEngine(graph, store, share_shape_classes=share,
                     profile_db=db, shader_cache=False)
    if profiler is not None:
        eng.profiler_factory = profiler
    stats = eng.decide(toks, n_little=2, calibrate_interference=False)
    return eng, stats


def test_shared_profiles_match_per_layer_plan(llm_graph, tmp_path):
    """Same Plan — choices, queues, makespan — whether profiles are shared
    per shape-class or measured per layer, given deterministic profiles."""
    graph, toks = llm_graph
    shared, _ = _engine(graph, toks, tmp_path / "a",
                        share=True, profiler=SyntheticProfiler)
    per_layer, _ = _engine(graph, toks, tmp_path / "b",
                           share=False, profiler=SyntheticProfiler)
    assert shared.plan.choices == per_layer.plan.choices
    assert shared.plan.big_prep == per_layer.plan.big_prep
    assert shared.plan.little_queues == per_layer.plan.little_queues
    assert shared.plan.est_makespan == pytest.approx(
        per_layer.plan.est_makespan, rel=1e-12)


def test_one_profile_per_shape_class_kernel(llm_graph, tmp_path):
    graph, toks = llm_graph
    eng, stats = _engine(graph, toks, tmp_path,
                         share=True, profiler=SyntheticProfiler)
    # embed / tblock / lmhead: identical tblocks collapse into one class
    assert stats["shape_classes"] == 3
    reps = {}
    for l in eng.layers:
        reps.setdefault(eng._sc_by_layer[l.spec.name], l)
    expect = sum(len(eng._kernels_for(l.spec)) for l in reps.values())
    assert stats["profile_calls"] == expect


def test_profiling_writes_nothing_to_model_store(llm_graph, tmp_path):
    graph, toks = llm_graph
    eng, _ = _engine(graph, toks, tmp_path,
                     share=True, profiler=SyntheticProfiler)
    chosen = sum(c.use_cache for c in eng.plan.choices)
    # only decide()'s materialization of CHOSEN entries writes the store —
    # candidate profiling goes through the profiler's scratch area
    assert eng.store.cache_write_count == chosen


def test_profile_db_roundtrip_zero_profile_calls(llm_graph, tmp_path):
    graph, toks = llm_graph
    db_path = tmp_path / "profile_db.json"
    eng1, s1 = _engine(graph, toks, tmp_path / "s", share=True,
                       db=db_path, profiler=SyntheticProfiler)
    assert s1["profile_calls"] > 0

    calls = []

    class Forbidden(SyntheticProfiler):
        def profile(self, spec, kernel, x):
            calls.append((spec.name, kernel.name))
            return super().profile(spec, kernel, x)

    eng2, s2 = _engine(graph, toks, tmp_path / "s", share=True,
                       db=db_path, profiler=Forbidden)
    assert calls == [] and s2["profile_calls"] == 0
    assert s2["profile_db_hits"] == s1["profile_calls"]
    assert eng2.plan.choices == eng1.plan.choices
    assert eng2.plan.little_queues == eng1.plan.little_queues


def test_force_reprofile_bypasses_db(llm_graph, tmp_path):
    graph, toks = llm_graph
    db_path = tmp_path / "profile_db.json"
    _engine(graph, toks, tmp_path / "s", share=True,
            db=db_path, profiler=SyntheticProfiler)
    eng = ColdEngine(graph, tmp_path / "s", share_shape_classes=True,
                     profile_db=db_path, shader_cache=False)
    eng.profiler_factory = SyntheticProfiler
    stats = eng.decide(toks, n_little=2, force_reprofile=True,
                       calibrate_interference=False)
    assert stats["profile_calls"] > 0 and stats["profile_db_hits"] == 0


def test_profile_db_scoped_by_host(tmp_path):
    db = ProfileDB(tmp_path / "db.json")
    p = OpProfile(layer="l", kernel="k", read_raw_s=1e-3, transform_s=1e-3,
                  read_cached_s=1e-3, exec_s=1e-3, compile_s=1e-3,
                  raw_bytes=4, transformed_bytes=4)
    db.put("sc0", "k", p)
    db.save()
    again = ProfileDB(tmp_path / "db.json")
    assert again.get("sc0", "k") is not None
    # a different host fingerprint never gets a FRESH hit: the donor host's
    # entries are served as STALE drift fallbacks (flagged for background
    # re-profiling) rather than adopted silently
    foreign = ProfileDB(tmp_path / "db.json")
    foreign.host = "elsewhere"
    foreign.entries = {}
    foreign._load()
    assert foreign.drifted_from == db.host
    assert foreign.get("sc0", "k") is not None
    assert foreign.stats["hits"] == 0
    assert foreign.stats["stale_hits"] == 1
    assert foreign.stale_pending() == [("sc0", "k")]
    # a fresh local measurement supersedes the drifted fallback
    foreign.put("sc0", "k", p)
    assert foreign.stale_pending() == []


# ---------------------------------------------------------------------------
# compile sharing
# ---------------------------------------------------------------------------
def test_one_compile_per_shape_class(llm_graph, tmp_path):
    graph, toks = llm_graph
    eng, _ = _engine(graph, toks, tmp_path,
                     share=True, profiler=SyntheticProfiler)
    eng._jitted_map(eng.plan.choices, toks)
    pairs = {(eng._sc_by_layer[l.spec.name], c.kernel)
             for l, c in zip(eng.layers, eng.plan.choices)}
    # one executable per pair: compiled here, or loaded from the shared
    # on-disk cache where an earlier run already compiled it
    s = eng.compile_cache.stats
    assert s["misses"] + s["disk_hits"] == len(pairs)
    # the N identical tblocks share ONE executable object
    jitted = eng._jitted_map(eng.plan.choices, toks)
    tbl = [jitted[l.spec.name] for l in eng.layers
           if l.spec.op_type == "tblock"]
    ch = {c.kernel for l, c in zip(eng.layers, eng.plan.choices)
          if l.spec.op_type == "tblock"}
    if len(ch) == 1:
        assert all(f is tbl[0] for f in tbl)


def test_compile_cache_version_guard(tmp_path):
    from repro.core import compile_cache as cc

    spec = LayerSpec("l", "linear", {"in_features": 4, "out_features": 4},
                     {"w": (4, 4)})
    import jax.numpy as jnp

    w = {"w": jnp.ones((4, 4), jnp.float32)}
    x = jnp.ones((2, 4), jnp.float32)
    fn = lambda w, x: x @ w["w"]

    cache = cc.CompileCache(tmp_path)
    cache.get("k", spec, fn, w, x, shape_class="sc")
    assert cache.stats["misses"] == 1
    # same key hits memory without compiling again
    cache.get("k", spec, fn, w, x, shape_class="sc")
    assert cache.stats["hits"] == 1 and cache.stats["misses"] == 1

    # fresh cache over the same dir: disk hit
    cache2 = cc.CompileCache(tmp_path)
    cache2.get("k", spec, fn, w, x, shape_class="sc")
    assert cache2.stats["disk_hits"] == 1 and cache2.stats["misses"] == 0

    # a different jax/jaxlib version must MISS cleanly (key changes)
    orig = cc._version_tag
    cc._version_tag = lambda: "jax-0.0.0/jaxlib-0.0.0"
    try:
        cache3 = cc.CompileCache(tmp_path)
        cache3.get("k", spec, fn, w, x, shape_class="sc")
        assert cache3.stats["misses"] == 1 and cache3.stats["disk_hits"] == 0
    finally:
        cc._version_tag = orig

    # ...and so must one built for another backend or device kind
    orig = cc.device_tag
    cc.device_tag = lambda: "tpu/TPU v5 lite"
    try:
        cache4 = cc.CompileCache(tmp_path)
        cache4.get("k", spec, fn, w, x, shape_class="sc")
        assert cache4.stats["misses"] == 1 and cache4.stats["disk_hits"] == 0
    finally:
        cc.device_tag = orig

    # an edited kernel body under the same kernel name and shape class is
    # another program: it misses on disk instead of loading the old one
    cache_e = cc.CompileCache(tmp_path)
    edited = cache_e.get("k", spec, lambda w, x: x @ w["w"] + 1.0, w, x,
                         shape_class="sc")
    assert cache_e.stats["misses"] == 1 and cache_e.stats["disk_hits"] == 0
    assert float(edited(w, x)[0, 0]) == 5.0
    cache_o = cc.CompileCache(tmp_path)
    orig_fn = cache_o.get("k", spec, fn, w, x, shape_class="sc")
    assert cache_o.stats["disk_hits"] == 1
    assert float(orig_fn(w, x)[0, 0]) == 4.0

    # an unreadable entry is counted, recompiled and rewritten
    for f in tmp_path.glob("*.xla"):
        f.write_bytes(b"not a pickle")
    cache5 = cc.CompileCache(tmp_path)
    cache5.get("k", spec, fn, w, x, shape_class="sc")
    assert cache5.stats["deserialize_failures"] == 1
    assert cache5.stats["misses"] == 1 and cache5.last_error
    cache6 = cc.CompileCache(tmp_path)
    cache6.get("k", spec, fn, w, x, shape_class="sc")
    assert cache6.stats["disk_hits"] == 1


def test_cache_invalidated_on_weight_update(tmp_path):
    """A second decide() over UPDATED raw weights must not keep serving the
    previous checkpoint's cached transformed entries (fingerprint sidecar):
    cold output must match the no-cache sequential path on the new model."""
    store = tmp_path / "s"
    graph1, toks = tiny_llm_graph(3, seed=0)
    eng1 = ColdEngine(graph1, store, shader_cache=False)
    eng1.decide(toks, n_little=2, calibrate_interference=False)

    graph2, _ = tiny_llm_graph(3, seed=1)  # same shapes, new weights
    eng2 = ColdEngine(graph2, store, shader_cache=False)
    eng2.decide(toks, n_little=2, calibrate_interference=False)
    r_cold = eng2.run_cold(toks)
    r_seq = eng2.run_cold(toks, mode="sequential")  # never reads the cache
    np.testing.assert_allclose(np.asarray(r_cold.output),
                               np.asarray(r_seq.output), atol=1e-5)


def test_unchanged_weights_skip_rematerialization(tmp_path):
    """Same weights, second decide(): cached entries are reused, zero new
    cache writes."""
    store = tmp_path / "s"
    graph, toks = tiny_llm_graph(3)
    eng1 = ColdEngine(graph, store, shader_cache=False)
    eng1.profiler_factory = SyntheticProfiler
    eng1.decide(toks, n_little=2, calibrate_interference=False)
    eng2 = ColdEngine(graph, store, shader_cache=False)
    eng2.profiler_factory = SyntheticProfiler
    eng2.decide(toks, n_little=2, calibrate_interference=False)
    assert eng2.plan.choices == eng1.plan.choices
    assert eng2.store.cache_write_count == 0


def test_compile_from_avatars_matches_real(tmp_path):
    """Executables lowered from ShapeDtypeStruct avatars run correctly on
    real weights — end-to-end cold run equals the reference forward."""
    import jax.numpy as jnp

    graph, toks = tiny_llm_graph(4)
    eng = ColdEngine(graph, tmp_path, shader_cache=False)
    eng.decide(toks, n_little=2, calibrate_interference=False)
    res = eng.run_cold(toks)
    res2 = eng.run_cold(toks, mode="sequential")
    np.testing.assert_allclose(np.asarray(res.output),
                               np.asarray(res2.output), atol=1e-5)
