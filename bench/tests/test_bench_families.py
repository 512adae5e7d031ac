"""A model family added as files: a family module declaring a kernel role
of its own runs a traced cell correct on the CPU, and a family without a
file stops a run before set-up."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

from test_bench_runs import (BENCH, REPO, _add_config, _run,  # noqa: F401
                             checkout)

# a family of its own: the dense decoder with one more kernel role, the
# output head in the prefill, found by its name
HEAD_ROLE = textwrap.dedent("""

    _dense_roles = roles


    def roles(m, mix, peak):
        head = flops.least_time(lm_head(m, 1), peak)
        return _dense_roles(m, mix, peak) + [
            Role("lmhead", "prefill",
                 lambda name, n: name.startswith("jit_lmhead") and n == 1,
                 lambda runs: sum(runs) * head)]
    """)


def _files(root: Path) -> dict:
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file()}


def test_new_files_add_a_family(checkout, monkeypatch):
    """A family file declaring a kernel role of its own, a configuration
    and a cell that use it, and a metric that reads the role make a traced
    cell that runs correct; no file of the harness changes. The CPU's
    trace has no device plane, so the trace read is the chip's recorded
    in ``data/trace_smollm_1req_spans.json``."""
    import tracing

    before = _files(checkout / "bench")
    families = checkout / "bench" / "families"
    (families / "dense_head.py").write_text(
        (families / "dense.py").read_text() + HEAD_ROLE)
    (checkout / "bench" / "metrics" / "lmhead_roofline.py").write_text(
        "def read(rec):\n"
        "    k = (rec['trace'] or {}).get('kernels', {}).get('lmhead')\n"
        "    return 100.0 * k['least_s'] / k['device_s'] if k else None\n")
    cell = _add_config(checkout, "tiny-nemo", family="dense_head")
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "lmhead_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "Kernels", "moves": "ttft_s",
        "workloads": [cell]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))
    after = _files(checkout / "bench")
    assert {p: after[p] for p in before} == before

    recorded = BENCH / "tests" / "data" / "trace_smollm_1req_spans.json"
    monkeypatch.setattr(tracing, "load",
                        lambda path: tracing.load_json(recorded))
    out = _run(checkout, cell, trace=True)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"lmhead_roofline"}
    assert 0 < out["metrics"]["lmhead_roofline"]["value"] < 100
    ops = [name for name, _ in out["breakdown"]["device_ops"]]
    assert any(n.startswith("lmhead jit_lmhead_bf16_cast(") for n in ops)


def test_a_family_without_a_file_fails_before_set_up(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(REPO / "BENCHMARK.json", root)
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = _add_config(root, "tiny-nemo", family="nonesuch")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed", "3",
         "--seconds", "1", "--trace", "0"], cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip()
    assert "FileNotFoundError" in p.stderr
    assert str(root / "bench" / "families" / "nonesuch.py") in p.stderr
    assert "set-up" not in p.stderr
