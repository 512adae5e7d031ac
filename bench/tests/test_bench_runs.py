"""Whole benchmark runs on the CPU at tiny widths: everything but the
look for a chip. A temporary copy of the benchmark gains tiny
configurations by adding files only; the timed path is then broken
underneath to see ``correct`` turn false."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))

# Mistral-Nemo's shape cut to tiny widths: the query width (heads x
# head_dim = 128) is not the model width (160), as 32 x 128 is not 5120;
# four query heads share a KV head, the MLP is 2.8 times as wide, the
# embedding and head are untied, RoPE's theta is 1e6. The limit sits
# between what sound runs and the fp8 control read at this size (see
# ``test_control_fails_where_the_program_passes``).
TINY = {
    "tiny-nemo": {"hidden_size": 160, "intermediate_size": 448,
                  "num_hidden_layers": 2, "num_attention_heads": 4,
                  "num_key_value_heads": 1, "head_dim": 32,
                  "vocab_size": 512, "tie_word_embeddings": False,
                  "rope_theta": 1e6, "limits": {"max_gap": 0.1}},
}
SEED = 2**31 + 77


def _add_config(root: Path, name: str, traffic: str = "cold_host"):
    conf = dict(TINY[name], source="test", reference="dense_decoder",
                rms_norm_eps=1e-5, torch_dtype="bfloat16")
    (root / "bench" / "configs" / f"{name}.json").write_text(
        json.dumps(conf))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": name, "source": "test",
                             "file": f"bench/configs/{name}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": f"{name}.{traffic}", "config": name,
                               "traffic": traffic, "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return f"{name}.{traffic}"


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """A copy of the benchmark's files, with the CPU in its peak table."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(REPO / "BENCHMARK.json", root)
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    peaks["cpu"] = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                    "hbm_bytes": 1e10, "source": "test"}
    (root / "bench" / "peaks.json").write_text(json.dumps(peaks))
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    return root


def _run(root, cell, seconds=1.0):
    import run

    return run.run(cell, SEED, seconds, False, root=root, require="cpu")


@pytest.mark.parametrize("where", ["repo", "bench_files_only"])
def test_run_without_a_tpu_prints_no_result(where, tmp_path):
    cwd = REPO
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if where == "bench_files_only":
        cwd = tmp_path
        shutil.copy(REPO / "BENCHMARK.json", cwd)
        shutil.copytree(BENCH, cwd / "bench")
        env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "smollm-360m.cold_host", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_new_files_add_a_cell(checkout):
    """A configuration, a traffic mix and a metric, each a file of its own,
    make a new cell; no harness file changes."""
    (checkout / "bench" / "traffic" / "short.json").write_text(json.dumps(
        {"arrival": "closed", "clients": 1, "prompt_tokens": 16,
         "new_tokens": 6, "sampling": "greedy", "page_cache": "warm",
         "compare_requests": 4}))
    (checkout / "bench" / "metrics" / "served_tokens.py").write_text(
        "def read(rec):\n"
        "    return sum(len(r['served']) for r in rec['requests'])\n")
    cell = _add_config(checkout, "tiny-nemo", "short")
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    bench["end_to_end"].append({"name": "served_tokens", "unit": "tokens",
                                "better": "higher", "bound": 0.25,
                                "source": "host_clock",
                                "workloads": [cell]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))

    out = _run(checkout, cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"ttft_s", "handoff_s", "tpot_ms",
                                   "setup_s", "served_tokens"}
    assert out["metrics"]["served_tokens"]["value"] == 6 * out["attempted"]
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    assert out["checks"]["max_gap"]["limit"] == 0.1


def _alter_token(monkeypatch):
    from repro.serving.server import BatchedServer

    orig = BatchedServer._pick
    monkeypatch.setattr(BatchedServer, "_pick", lambda self, req, row: (
        orig(self, req, row) + 1) % 512)


def _decode_state_unchanged(monkeypatch):
    from repro.models import transformer as T

    orig = T.decode_step
    monkeypatch.setattr(T, "decode_step", lambda p, s, b, pos, cfg: (
        orig(p, s, b, pos, cfg)[0], s))


def _prefill_block_skipped(monkeypatch):
    from repro.core import llm_graph

    monkeypatch.setattr(llm_graph, "_block_forward",
                        lambda w, x, cfg, dtype: x)


def _blocks_staged_as_int8(monkeypatch):
    """Every block kernel quietly stores, stages and runs per-channel int8
    weights under its own name, as a faster plan would."""
    from repro.core import llm_graph

    for cls in (llm_graph.TBlockF32Direct, llm_graph.TBlockBf16):
        monkeypatch.setattr(cls, "bits", 8, raising=False)
        monkeypatch.setattr(cls, "transform", llm_graph.TBlockInt8.transform)
        monkeypatch.setattr(cls, "execute", llm_graph.TBlockInt8.execute)


# the number each fault has to push past its limit
FAULTS = {_alter_token: "max_gap", _decode_state_unchanged: "max_gap",
          _prefill_block_skipped: "max_gap",
          _blocks_staged_as_int8: "staged_below_dtype_bytes"}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_broken_timed_path_is_not_correct(fault, checkout, monkeypatch):
    cell = _add_config(checkout, "tiny-nemo")
    fault(monkeypatch)
    out = _run(checkout, cell)
    assert not out["correct"]
    check = out["checks"][FAULTS[fault]]
    assert check["value"] > check["limit"], out["checks"]


def test_nemo_shapes_match_the_reference_through_the_cold_graph(tmp_path):
    """Prefill logits streamed through the cold graph, and decode logits
    through the KV cache, against the float32 reference."""
    import compare
    import modelcfg
    import run
    import weights
    from repro.core.llm_graph import build_llm_graph
    from repro.executor.llm_bridge import cold_start_llm
    from repro.executor.server import ColdServer

    path = tmp_path / "tiny-nemo.json"
    path.write_text(json.dumps(dict(
        TINY["tiny-nemo"], reference="dense_decoder", rms_norm_eps=1e-5,
        torch_dtype="bfloat16")))
    m = modelcfg.model(path, "tiny-nemo")
    cfg = modelcfg.arch_config(m)
    assert cfg.num_heads * cfg.head_dim != cfg.d_model
    params = weights.make(SEED, m)
    graph, _ = build_llm_graph(cfg, params)
    server = ColdServer(tmp_path / "server", n_little=2)
    eng = server.add_model("m", graph)
    prompt = np.random.default_rng(0).integers(0, 512, 24).astype(np.int32)
    server.decide("m", prompt[None], calibrate_interference=False)
    res = cold_start_llm(eng, cfg, prompt, max_new_tokens=8, n_little=2,
                         server=server, model_name="m", keep_logits=True)

    ref = run.load_module(BENCH / "refs" / "dense_decoder.py")
    rows, positions = compare.served_rows([prompt], [res.tokens])
    want = np.asarray(ref.logits_at(params, m, rows,
                                    list(range(rows.shape[1]))))[0]
    prefill = np.asarray(res.run.output)[0]
    S = prompt.size

    def rel(got, ref):
        return float(np.abs(got - ref).max() / np.abs(ref).max())

    # bf16 serving against float32: chip_smoke's 0.05, far above the
    # 0.01-0.02 that bf16 rounding gives
    assert rel(prefill, want[:S]) < 0.05
    assert rel(res.logits[1:], want[positions[1:]]) < 0.05
    assert res.tokens[0] == int(prefill[-1].argmax())


def test_control_fails_where_the_program_passes(checkout):
    """At tiny widths, on three seeds: sound runs are correct; the
    program's own int8 path is not; the fp8 control (the reference one
    precision step below bf16) reads above the limit."""
    import control

    cell = _add_config(checkout, "tiny-nemo")
    limit = TINY["tiny-nemo"]["limits"]["max_gap"]
    for seed in (1, 2, 3):
        r = control.readings(cell, seed, 4, root=checkout, require="cpu")
        assert r["program"]["correct"], r
        assert r["program"]["max_gap"] <= limit < r["fp8"], r
        assert "int8" in r["int8_path"]["kernels"], r
        assert not r["int8_path"]["correct"], r
        assert r["int8_path"]["staged_below_dtype_bytes"] > 0, r
