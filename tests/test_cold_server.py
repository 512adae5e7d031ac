"""ColdServer: multi-model cold serving on one pool — admission control,
shared ProfileDB, LRU residency, and the cold-LLM serving bridge."""
import threading

import numpy as np
import pytest

from repro.executor.server import ColdServer


@pytest.fixture(scope="module")
def two_model_server(tmp_path_factory):
    from repro.models.cnn import build_cnn

    srv = ColdServer(tmp_path_factory.mktemp("srv"), n_little=2,
                     max_concurrent_preps=1)
    inputs = {}
    for name, arch in (("mnet", "mobilenet"), ("snet", "squeezenet")):
        layers, x = build_cnn(arch, image=16, width=0.25)
        srv.add_model(name, layers)
        srv.decide(name, x, n_little=2)
        inputs[name] = x
    return srv, inputs


def test_two_models_cold_start_concurrently_no_crosstalk(two_model_server):
    srv, inputs = two_model_server
    isolated = {n: srv.cold_start(n, x).result() for n, x in inputs.items()}
    results = {}

    def go(name):
        results[name] = srv.cold_start(name, inputs[name]).result()

    ts = [threading.Thread(target=go, args=(n,)) for n in inputs]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    for name in inputs:
        np.testing.assert_array_equal(np.asarray(results[name].output),
                                      np.asarray(isolated[name].output))
        # traces cover exactly this model's layers — no cross-talk
        assert {t.layer for t in results[name].traces} == \
            {t.layer for t in isolated[name].traces}
        # resident weights belong to the right model
        assert set(results[name].weights) == \
            {l.spec.name for l in srv.engines[name].layers}
    assert srv.stats["max_active_preps"] <= 1


def test_admission_blocks_second_prep(two_model_server):
    """With cap=1, the second cold start must not enter its prep phase
    while the first is still prepping."""
    srv, inputs = two_model_server
    order = []

    def go(name):
        t = srv.cold_start(name, inputs[name])
        order.append(("admitted", name))
        t.result()

    ts = [threading.Thread(target=go, args=(n,)) for n in inputs]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert srv.stats["max_active_preps"] <= 1
    assert len(order) == 2


def test_lru_eviction_under_memory_budget(tmp_path):
    from repro.models.cnn import build_cnn

    srv = ColdServer(tmp_path, n_little=2, max_concurrent_preps=2)
    for name, arch in (("m1", "mobilenet"), ("m2", "squeezenet")):
        layers, x = build_cnn(arch, image=16, width=0.25)
        srv.add_model(name, layers)
        srv.decide(name, x, n_little=2)
        srv.cold_start(name, x).result()
        if name == "m1":
            # budget just under both models: the second arrival must evict
            srv.memory_budget_bytes = srv.resident_bytes() + 1
    assert srv.resident_models() == ["m2"]
    assert srv.stats["evictions"] == 1
    # evicted model serves cold again; resident model serves warm
    layers, x1 = build_cnn("mobilenet", image=16, width=0.25)
    assert srv.warm_run("m1", x1) is None
    r = srv.run("m1", x1)
    assert r.output is not None


def test_shared_profile_db_second_model_zero_profile_calls(tmp_path):
    """Satellite: one user-level ProfileDB for all managed engines — a
    sibling model with the same shape classes performs zero profile
    calls."""
    from repro.core.llm_graph import tiny_llm_graph

    srv = ColdServer(tmp_path, n_little=2)
    g1, toks = tiny_llm_graph(4, seed=0)
    g2, _ = tiny_llm_graph(4, seed=1)     # same shapes, different weights
    srv.add_model("m1", g1)
    srv.add_model("m2", g2)
    s1 = srv.decide("m1", toks, n_little=2)
    s2 = srv.decide("m2", toks, n_little=2)
    assert s1["profile_calls"] > 0
    assert s2["profile_calls"] == 0
    assert s2["profile_db_hits"] > 0
    # both engines share the one DB object at the server root
    assert srv.engines["m1"].profile_db is srv.engines["m2"].profile_db
    assert srv.profile_db.path.parent == srv.root


def test_cold_llm_first_token_before_last_layer_prep(tmp_path):
    """The serving bridge: first token from the streamed prefill precedes
    the last layer's decode-path prep; weight preps overlap the exec
    chain (execute-as-you-load); decode continues via BatchedServer."""
    from repro.configs import get_config
    from repro.core.llm_graph import tiny_llm_graph
    from repro.executor.llm_bridge import cold_start_llm

    cfg = get_config("smollm-360m").reduced(
        num_layers=4, d_model=128, d_ff=256, num_heads=2, num_kv_heads=1,
        head_dim=64, vocab_size=512)
    graph, toks = tiny_llm_graph(4)
    srv = ColdServer(tmp_path, n_little=2)
    eng = srv.add_model("llm", graph)
    srv.decide("llm", toks, n_little=2)
    res = cold_start_llm(eng, cfg, toks[0], max_new_tokens=3, n_little=2,
                         server=srv, model_name="llm")
    assert res.first_token_s < res.decode_prep_s <= res.decode_ready_s
    assert res.overlapped_layers >= 1
    assert len(res.tokens) == 3
    assert all(0 <= t < cfg.vocab_size for t in res.tokens)
    # the decoded continuation came through the BatchedServer bridge with
    # the packed params: the packed first token matches the streamed one
    assert res.tokens[0] == res.first_token


def test_batched_server_run_until_drained_returns_finished():
    """Regression: run_until_drained used to always return [] — it must
    return the requests that finished during the call."""
    import jax

    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serving import BatchedServer, Request

    cfg = get_config("smollm-360m").reduced(num_layers=2, vocab_size=64)
    params = T.init_params(jax.random.PRNGKey(3), cfg)
    srv = BatchedServer(params, cfg, max_batch=2, max_len=64)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, 64, size=5),
                    max_new_tokens=4) for i in range(3)]
    for r in reqs:
        srv.submit(r)
    done = srv.run_until_drained()
    assert sorted(r.rid for r in done) == [0, 1, 2]
    assert all(r.done_s is not None for r in done)
    # a second drain with nothing queued returns nothing new
    assert srv.run_until_drained() == []
