"""The jitted decode step is built once per configuration and kept in a
memo its owner holds: a ``ColdEngine``'s ``decode_steps``, which every
``BatchedServer`` of the engine's cold starts takes its step from
(``serving.server.decode_step_for``)."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.llm_graph import tiny_llm_graph
from repro.executor.llm_bridge import cold_start_llm
from repro.executor.server import ColdServer
from repro.models import transformer as T
from repro.serving.server import (
    COUNTERS, BatchedServer, Request, decode_step_for,
)

LAYERS = 2


def _cfg():
    """``tiny_llm_graph``'s configuration."""
    return get_config("smollm-360m").reduced(
        num_layers=LAYERS, d_model=128, d_ff=256, num_heads=2,
        num_kv_heads=1, head_dim=64, vocab_size=512)


def _counts():
    return COUNTERS["decode_step_builds"], COUNTERS["decode_step_reuses"]


def _private(params, cfg, max_len):
    """A server with a ``jax.jit`` of the step of its own, as every server
    had before the step was shared."""
    srv = BatchedServer(params, cfg, max_batch=1, max_len=max_len)

    def decode_step(p, s, b, pos):
        return T.decode_step(p, s, b, pos, cfg)

    srv._decode = jax.jit(decode_step)
    return srv


def _serve(srv, prompt):
    req = Request(rid=0, prompt=prompt, max_new_tokens=5, out_logits=[])
    srv.submit(req)
    assert srv.run_until_drained() == [req]
    return req.out_tokens, np.stack(req.out_logits)


def test_two_cold_starts_of_one_config_share_one_step(tmp_path):
    cfg = _cfg()
    graph, toks = tiny_llm_graph(LAYERS)
    srv = ColdServer(tmp_path, n_little=2)
    eng = srv.add_model("llm", graph)
    srv.decide("llm", toks, n_little=2)
    builds, reuses = _counts()
    runs = []
    for expect in ((builds + 1, reuses), (builds + 1, reuses + 1)):
        srv.evict("llm")
        runs.append(cold_start_llm(eng, cfg, toks[0], max_new_tokens=4,
                                   n_little=2, server=srv, model_name="llm"))
        assert _counts() == expect
    assert list(eng.decode_steps) == [cfg]
    traced = [any("decode_step" in t.detail for t in r.spans
                  if t.kind == "compile") for r in runs]
    assert traced == [True, False]
    assert runs[0].tokens == runs[1].tokens


@pytest.mark.parametrize("seed", [0, 1])
def test_shared_step_serves_as_a_private_jit(seed):
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    prompt = np.random.default_rng(seed).integers(0, cfg.vocab_size, 6)
    steps = {}
    shared = BatchedServer(params, cfg, max_batch=1, max_len=16, steps=steps)
    private = _private(params, cfg, 16)
    assert shared._decode is steps[cfg]
    assert private._decode is not shared._decode
    tokens, rows = _serve(shared, prompt)
    tokens_p, rows_p = _serve(private, prompt)
    assert tokens == tokens_p
    np.testing.assert_array_equal(rows, rows_p)


def test_configs_that_differ_in_one_field_get_two_steps():
    cfg = _cfg()
    steps = {}
    builds, reuses = _counts()
    step = decode_step_for(cfg, steps)
    # an equal configuration, another object: the same step
    assert decode_step_for(dataclasses.replace(cfg), steps) is step
    other = decode_step_for(
        dataclasses.replace(cfg, norm_eps=cfg.norm_eps * 10), steps)
    assert other is not step and len(steps) == 2
    # without a memo, a step of its own
    assert decode_step_for(cfg) is not step
    assert _counts() == (builds + 3, reuses + 1)


def test_a_second_max_len_on_one_config_decodes_as_a_private_jit():
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(2), cfg)
    prompt = np.random.default_rng(2).integers(0, cfg.vocab_size, 6)
    steps = {}
    for max_len in (16, 24):
        tokens, rows = _serve(BatchedServer(params, cfg, max_batch=1,
                                            max_len=max_len, steps=steps),
                              prompt)
        tokens_p, rows_p = _serve(_private(params, cfg, max_len), prompt)
        assert tokens == tokens_p
        np.testing.assert_array_equal(rows, rows_p)
    assert len(steps) == 1
