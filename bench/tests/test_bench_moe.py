"""The sparse-expert family (``families/moe.py``) on the CPU at tiny
widths, through the benchmark's own path, against its float32 reference
(``refs/moe_decoder.py``)."""
import json
import math

import numpy as np
import pytest

from test_bench_runs import (BENCH, REPO, SEED, _run,  # noqa: F401
                             checkout)

MELLUM = REPO / "bench" / "configs" / "mellum2-12b-a2.5b-ep8.json"
# Mellum 2's block cut to tiny widths: one whole period of three sliding
# layers (window 8, so that it bites on a 24-token prompt) and a full
# layer with YaRN (its ramp spans frequencies 0..5 at head_dim 32), 16
# experts routed top 4, of which this chip holds 4; untied head. Served in
# bf16, a top-k pick that rounding flips between two nearly tied experts
# moves a served token's reference logit by up to 0.37 below the best at
# these widths (seeds 1-10 on the CPU), so the limit is 0.5; the tight
# comparison is the float32 one below
SLIDING, FULL = "sliding_attention", "full_attention"
TINY_MOE = {
    "hidden_size": 128, "moe_intermediate_size": 64, "intermediate_size": 256,
    "num_hidden_layers": 4, "layer_types": [SLIDING] * 3 + [FULL],
    "mlp_layer_types": ["sparse"] * 4, "num_attention_heads": 4,
    "num_key_value_heads": 1, "head_dim": 32, "vocab_size": 512,
    "tie_word_embeddings": False, "rms_norm_eps": 1e-6,
    "num_experts": 4, "published": {"num_experts": 16},
    "num_experts_per_tok": 4, "norm_topk_prob": True, "sliding_window": 8,
    "rope_parameters": {
        FULL: {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 16.0,
               "original_max_position_embeddings": 64, "beta_fast": 32.0,
               "beta_slow": 1.0, "attention_factor": 0.1 * math.log(16) + 1},
        SLIDING: {"rope_type": "default", "rope_theta": 10000.0}},
    "torch_dtype": "bfloat16", "family": "moe", "reference": "moe_decoder",
    "limits": {"max_gap": 0.5},
}


def _add_moe_config(root, name="tiny-mellum", traffic="cold_host"):
    (root / "bench" / "configs" / f"{name}.json").write_text(
        json.dumps(dict(TINY_MOE, source="test")))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": name, "source": "test",
                             "file": f"bench/configs/{name}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": f"{name}.{traffic}", "config": name,
                               "traffic": traffic, "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return f"{name}.{traffic}"


def _tiny(tmp_path, **keys):
    import modelcfg

    path = tmp_path / "tiny-mellum.json"
    path.write_text(json.dumps(dict(TINY_MOE, **keys)))
    m = modelcfg.model(path, "tiny-mellum")
    return m, modelcfg.family(m)


def test_reference_yarn_matches_a_hand_table():
    """Mellum 2's full-layer RoPE at head_dim 128: YaRN's correction range
    is [18, 35]; below it the frequencies are RoPE's, above it divided by
    16, in between blended linearly; cos and sin scale by 1.2773."""
    import run

    ref = run.load_module(BENCH / "refs" / "moe_decoder.py")
    rope = json.loads(MELLUM.read_text())["rope_parameters"]
    freq, factor = ref.inv_freq(rope[FULL], 128)
    theta = 500000.0
    table = {0: 1.0, 9: theta ** (-18 / 128), 18: theta ** (-36 / 128),
             # ramp (i - 18) / 17 at i = 20 and 30
             20: theta ** (-40 / 128) * (1 - 2 / 17 + 2 / 17 / 16),
             30: theta ** (-60 / 128) * (1 - 12 / 17 + 12 / 17 / 16),
             35: theta ** (-70 / 128) / 16, 63: theta ** (-126 / 128) / 16}
    for i, want in table.items():
        assert freq[i] == pytest.approx(want, rel=1e-12), i
    assert factor == 1.2772588722239782
    plain, one = ref.inv_freq(rope[SLIDING], 128)
    assert one == 1.0 and plain[20] == pytest.approx(theta ** (-40 / 128))


def _served(tmp_path, dtype):
    """A tiny Mellum served in ``dtype`` through the cold graph: (model,
    prompt, its cold start with logits, the reference's logits at every
    position of the prompt and the served tokens)."""
    import compare
    import run
    from repro.core.llm_graph import build_llm_graph
    from repro.executor.llm_bridge import cold_start_llm
    from repro.executor.server import ColdServer

    m, family = _tiny(tmp_path, torch_dtype=dtype)
    cfg = family.arch_config(m)
    assert cfg.layer_kinds() == ("local",) * 3 + ("attn",)
    params = family.make(SEED, m)
    graph, _ = build_llm_graph(cfg, params)
    assert [l.spec.op_type for l in graph[1:-1]] == ["moeblock"] * 4
    server = ColdServer(tmp_path / "server", n_little=2)
    eng = server.add_model("m", graph)
    prompt = np.random.default_rng(0).integers(0, 512, 24).astype(np.int32)
    server.decide("m", prompt[None], calibrate_interference=False)
    res = cold_start_llm(eng, cfg, prompt, max_new_tokens=8, n_little=2,
                         server=server, model_name="m", keep_logits=True)
    ref = run.load_module(BENCH / "refs" / "moe_decoder.py")
    rows, positions = compare.served_rows([prompt], [res.tokens])
    want = np.asarray(ref.logits_at(params, m, rows,
                                    list(range(rows.shape[1]))))[0]
    return prompt, res, want, positions


def misses(got, want) -> np.ndarray:
    """Per position, the largest logit error over the logits' range."""
    return np.abs(got - want).max(-1) / np.abs(want).max()


def test_tiny_mellum_matches_the_reference_through_the_cold_graph(tmp_path):
    """Prefill logits streamed through the cold graph's ``moeblock``
    units, and decode logits through the KV cache, against the float32
    reference's full forward: the window bites (24 positions past a
    window of 8) and YaRN is on in the full layer. The model is served in
    float32 here, so that every routing decision is the reference's: in
    bf16, rounding flips a top-k pick now and then where two experts'
    router probabilities nearly tie, and the flip moves the logits of
    that token and of every later one by up to 0.1 of their range at
    these widths, as much as a lost mechanism would. In float32 the
    program differs from the reference by float32 rounding alone, 1e-5
    of the range; 1e-3 leaves room for the order of the sums, and a lost
    window, YaRN or held expert misses by 0.05 or more
    (``test_a_lost_mechanism_misses_the_reference``)."""
    prompt, res, want, positions = _served(tmp_path, "float32")
    prefill = np.asarray(res.run.output)[0]
    assert misses(prefill, want[:prompt.size]).max() < 1e-3
    assert misses(res.logits[1:], want[positions[1:]]).max() < 1e-3
    assert res.tokens[0] == int(prefill[-1].argmax())


def _lose_window(cfg):
    import dataclasses

    return dataclasses.replace(cfg, sliding_window=None)


def _lose_yarn(cfg):
    import dataclasses

    return dataclasses.replace(cfg, rope_yarn=None)


def _lose_held_expert(cfg):
    import dataclasses

    return dataclasses.replace(cfg, held_experts=cfg.held_experts[1:] + (15,))


@pytest.mark.parametrize("lose", [_lose_window, _lose_yarn,
                                  _lose_held_expert])
def test_a_lost_mechanism_misses_the_reference(lose, tmp_path):
    """The comparison above sees each mechanism: the program, in float32,
    run without the window, without YaRN or with one held expert swapped
    for another misses the reference by 0.05 or more (0.05-0.3 without
    YaRN, 0.4-1.2 without the others, on the seeds tried), where it is
    otherwise within 1e-3."""
    import jax
    import jax.numpy as jnp
    import run
    from repro.models import transformer as T

    m, family = _tiny(tmp_path, torch_dtype="float32")
    params = family.make(SEED, m)
    wide = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    prompt = np.random.default_rng(0).integers(0, 512, (1, 24))
    ref = run.load_module(BENCH / "refs" / "moe_decoder.py")
    want = np.asarray(ref.logits_at(params, m, prompt, list(range(24))))[0]

    def miss(cfg):
        got, _, _ = jax.jit(lambda p, t: T.forward(p, {"tokens": t}, cfg))(
            wide, jnp.asarray(prompt))
        return misses(np.asarray(got)[0], want).max()

    cfg = family.arch_config(m)
    assert miss(cfg) < 1e-3
    assert miss(lose(cfg)) > 0.05


def test_tiny_mellum_cell_runs_correct(checkout):
    """The cell's whole run on the CPU, served in bf16 as deployed, is
    correct against the reference; the program's staging counter gives
    the held experts' bytes per cold start."""
    import run
    from repro.core.staging import COUNTERS

    COUNTERS.clear()
    cell = _add_moe_config(checkout)
    out = _run(checkout, cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"ttft_s", "handoff_s", "tpot_ms",
                                   "setup_s"}
    # 4 layers of 4 held experts of 3 x 128 x 64 weights, staged as bf16
    # or f32 by the kernel each layer's plan picked
    per_layer = 4 * 3 * 128 * 64
    gb = run.load_module(BENCH / "metrics" / "expert_stage_gb.py").read({})
    assert gb * 1e9 in {per_layer * (2 * a + 4 * (4 - a)) for a in range(5)}


def _experts_skipped(monkeypatch):
    from repro.models import moe

    monkeypatch.setattr(moe, "held_experts_apply",
                        lambda p, x, cfg: x * 0)


def _window_dropped(monkeypatch):
    from repro.models import transformer as T

    monkeypatch.setattr(T, "NO_WINDOW", 8)


@pytest.mark.parametrize("fault", [_experts_skipped, _window_dropped])
def test_a_broken_expert_path_is_not_correct(fault, checkout, monkeypatch):
    """The program with its held experts skipped, or decoding every layer
    with the local window, serves tokens the reference puts further than
    the limit below its best."""
    cell = _add_moe_config(checkout)
    fault(monkeypatch)
    out = _run(checkout, cell)
    assert not out["correct"]
    assert out["checks"]["max_gap"]["value"] > 0.5, out["checks"]


def _trace(events_by_phase):
    """A device trace in ``tracing.load``'s form: per phase, executables
    run back to back, each 1 ms, the phases marked on the host."""
    dev, host, t = [], [("bench.window", 0, 10**12)], 0
    for phase, events in events_by_phase:
        start = t
        for name, n in events:
            for _ in range(n):
                dev.append((name, t, t + 10**6))
                t += 10**6
        host.append((phase, start, t))
    return [dev], host


def test_roles_find_both_block_kinds_and_the_decode_step(tmp_path):
    """Sliding and full blocks compile apart, to executables of one name
    that differ in their program's fingerprint: the roles find each by
    its number of runs, and the roofline reader takes both."""
    import run
    import tracing

    m, family = _tiny(tmp_path)
    mix = {"prompt_tokens": 128, "new_tokens": 32}
    peak = {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}
    prefill = [("jit_moeblock_bf16_cast(11)", 3),
               ("jit_moeblock_bf16_cast(22)", 1), ("jit_embed_direct(3)", 1)]
    decode = [("jit_decode_step(5)", 159), ("jit_convert_element_type(6)", 4)]
    devices, host = _trace([("bench.prefill", prefill),
                            ("bench.decode", decode)] * 2)
    red = tracing.reduce(devices, host, family.roles(m, mix, peak))
    k = red["kernels"]
    assert k["moeblock_local"]["name"].endswith("(11)")
    assert k["moeblock_full"]["name"].endswith("(22)")
    assert k["moeblock_local"]["runs"] == 6 and k["moe_decode_step"]["runs"] \
        == 318
    rec = {"trace": red}
    share = run.load_module(BENCH / "metrics" / "moeblock_roofline.py").read(
        rec)
    want = 100 * (k["moeblock_local"]["least_s"] + k["moeblock_full"][
        "least_s"]) / 8e-3
    assert share == pytest.approx(want) and 0 < share < 100
    assert 0 < run.load_module(BENCH / "metrics" /
                               "moe_decode_roofline.py").read(rec) < 100
    for name in ("moeblock_roofline", "moe_decode_roofline"):
        assert run.load_module(BENCH / "metrics" / f"{name}.py").read(
            {"trace": None}) is None


def test_work_counts_at_mellums_widths():
    """A block is 142 MB of bf16 weights, 70% of them held experts, all
    read at a 128-token prompt; a decode step at the expected routing, one
    held expert a layer, reads 0.89 GB of the 1.59 GB it holds."""
    import modelcfg

    m = modelcfg.model(MELLUM, "mellum2-12b-a2.5b-ep8")
    family = modelcfg.family(m)
    blk = family.moeblock(m, 128)
    assert blk["bytes"] == pytest.approx(142e6, rel=0.01)
    experts = 8 * family._expert_weights(m) * 2
    assert experts / blk["bytes"] == pytest.approx(0.70, abs=0.01)
    step = family.decode_step(m, 0)
    assert step["bytes"] == pytest.approx(0.89e9, rel=0.01)
    held = (8 * (blk["bytes"] - 2 * 2 * 128 * 2304)
            + family.lm_head(m)["bytes"])
    assert held == pytest.approx(1.59e9, rel=0.01)
