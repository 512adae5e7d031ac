"""The cold path's spans: one ``OpTrace`` per step in the job's record,
and the same step as a ``nnv12.<kind>`` event in a profiler trace."""
import gc
import glob
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.compile_cache import CompileCache
from repro.core.llm_graph import tiny_llm_graph
from repro.executor.graph import collections_between, serving, span
from repro.executor.llm_bridge import cold_start_llm
from repro.executor.server import ColdServer

LAYERS = 4
NEW_TOKENS = 5
HANDOFF = ["first_token", "handoff.copy", "handoff.stack", "handoff.replay"]


def _cfg():
    return get_config("smollm-360m").reduced(
        num_layers=LAYERS, d_model=128, d_ff=256, num_heads=2,
        num_kv_heads=1, head_dim=64, vocab_size=512)


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    """(server, engine, prompt ids) of the tiny LLM, decided; the engine
    has built no decode step yet."""
    graph, toks = tiny_llm_graph(LAYERS)
    srv = ColdServer(tmp_path_factory.mktemp("server"), n_little=2)
    eng = srv.add_model("llm", graph)
    srv.decide("llm", toks, n_little=2)
    return srv, eng, toks


def _cold_start(model):
    srv, eng, toks = model
    srv.evict("llm")
    return cold_start_llm(eng, _cfg(), toks[0],
                          max_new_tokens=NEW_TOKENS, n_little=2,
                          server=srv, model_name="llm")


@pytest.fixture(scope="module")
def cold(model, tmp_path_factory):
    """The engine's first cold start, traced by the profiler:
    (result, its job's seq, the profiler's host events)."""
    from jax.profiler import ProfileData

    logdir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(logdir)):
        res = _cold_start(model)
    xplane, = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    events = [(e.name, dict(e.stats), e.duration_ns / 1e9)
              for plane in ProfileData.from_file(xplane).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("nnv12.")]
    seq = next(t.request for t in res.spans if t.kind == "execute")
    return res, seq, events


def test_every_span_carries_the_request_and_a_known_parent(cold):
    res, seq, _ = cold
    assert {t.request for t in res.spans} == {seq}
    kinds = {t.kind for t in res.spans}
    assert {"read", "stage", "execute", "pack", "decode.step",
            "decode.pick", "decode.wait", *HANDOFF} <= kinds
    assert {t.parent for t in res.spans} - {None} <= kinds


def test_handoff_spans_come_in_order(cold):
    res, _, _ = cold
    one = {k: next(t for t in res.spans if t.kind == k) for k in HANDOFF}
    for a, b in zip(HANDOFF, HANDOFF[1:]):
        assert one[a].end <= one[b].start, (a, b)
    # the replay ends at the second token's pick: every tick follows it
    steps = [t for t in res.spans if t.kind == "decode.step"]
    assert one["handoff.replay"].end <= min(t.start for t in steps)
    assert all(t.parent is None for t in steps)


def test_one_pick_per_decoded_token(cold):
    res, _, _ = cold
    picks = [t for t in res.spans if t.kind == "decode.pick"]
    assert len(res.tokens) == NEW_TOKENS
    assert len(picks) == len(res.tokens) - 1
    # each pick holds one wait for its token id
    waits = [t for t in res.spans if t.kind == "decode.wait"]
    assert len(waits) == len(picks)
    assert all(w.parent == "decode.pick" for w in waits)
    assert all(p.start <= w.start and w.end <= p.end
               for p, w in zip(sorted(picks, key=lambda t: t.start),
                               sorted(waits, key=lambda t: t.start)))
    # one tick for each token after the replay's
    assert len([t for t in res.spans
                if t.kind == "decode.step"]) == len(res.tokens) - 2


@pytest.mark.parametrize("kind", ["compile", "gc"])
def test_compiles_and_collections_become_spans(model, cold, kind):
    res, seq, _ = cold
    if kind == "compile":
        # an engine's first cold start of a configuration traces its decode
        # step; a second one takes the step built then and traces none of it
        found = [t for t in res.spans if t.kind == "compile"]
        assert any("decode_step" in t.detail for t in found)
        assert all(t.end >= t.start for t in found)
        again = _cold_start(model)
        assert again.tokens == res.tokens
        assert not any("decode_step" in t.detail for t in again.spans
                       if t.kind == "compile")
    else:
        t0 = time.perf_counter()
        gc.collect()
        found = collections_between(t0, float("inf"))
        assert any(c.detail.startswith("gen2 collected ") for c in found)


@pytest.mark.parametrize("kind", ["stage", "handoff.copy"])
def test_profiler_events_match_their_spans(cold, kind):
    res, seq, events = cold
    mine = sorted(t.end - t.start for t in res.spans if t.kind == kind)
    traced = sorted(d for name, stats, d in events
                    if name == f"nnv12.{kind}")
    assert mine and len(traced) == len(mine)
    assert np.allclose(traced, mine, rtol=0, atol=1e-3)
    assert all(stats.get("request") == seq for name, stats, _ in events
               if name == f"nnv12.{kind}")


def test_executable_cache_time_is_a_compile_span(tmp_path):
    """``CompileCache.get`` times one compile, and its stats and the
    request's ``compile`` span read the same interval."""
    from repro.core.registry import LayerSpec
    from repro.core.llm_graph import EmbedDirect

    class Job:
        seq, t0, traces = 7, 0.0, []

    spec = LayerSpec("embed", "embed", weight_shapes={"embed": (8, 4)})
    w = {"embed": jax.ShapeDtypeStruct((8, 4), np.float32)}
    x = jax.ShapeDtypeStruct((1, 3), np.int32)
    cache = CompileCache(tmp_path)
    with serving(Job):
        cache.get("direct", spec, EmbedDirect().program(spec), w, x)
    own = [t for t in Job.traces if t.kind == "compile" and t.parent is None]
    assert len(own) == 1 and own[0].detail == "executable_cache direct"
    assert own[0].end - own[0].start == pytest.approx(
        cache.stats["compile_s"], abs=1e-9)
    # the lowering and backend compile inside it are its children
    assert {t.parent for t in Job.traces} == {None, "compile"}


@pytest.mark.parametrize("which", ["layer", "decode"])
def test_programs_have_stable_names(which):
    if which == "layer":
        from repro.core.llm_graph import EmbedDirect
        from repro.core.registry import LayerSpec

        fn = EmbedDirect().program(LayerSpec("embed", "embed"))
        text = jax.jit(fn).lower(
            {"embed": jax.ShapeDtypeStruct((8, 4), np.float32)},
            jax.ShapeDtypeStruct((1, 3), np.int32)).as_text()
        assert "module @jit_embed_direct" in text
    else:
        from repro.models import transformer as T
        from repro.serving.server import BatchedServer

        cfg = _cfg()
        srv = BatchedServer(T.init_params(jax.random.PRNGKey(0), cfg), cfg,
                            max_batch=1, max_len=8)
        text = srv._decode.lower(
            srv.params, srv.state,
            {"tokens": jax.numpy.zeros((1, 1), jax.numpy.int32)},
            jax.numpy.int32(0)).as_text()
        assert "module @jit_decode_step" in text


def test_spans_without_a_job_record_nothing():
    with span("decode.step") as sp:
        pass
    assert sp.job is None and sp.parent is None and sp.seconds >= 0
