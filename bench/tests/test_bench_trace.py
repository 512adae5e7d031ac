"""The trace reduction, on a trace recorded on the chip.

``data/trace_smollm_1req.json`` holds the device executables (``XLA
Modules``) of one cold smollm-360m request recorded on a TPU v5e, and the
harness's host marks: the window, and the prefill and decode phases
(placed from the executables' order: prefill up to the output head's run,
decode from the first decode step on).
"""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import flops  # noqa: E402
import modelcfg  # noqa: E402
import tracing  # noqa: E402

DATA = BENCH / "tests" / "data" / "trace_smollm_1req.json"
MODEL = modelcfg.model(BENCH / "configs" / "smollm-360m.json", "smollm")
MIX = {"prompt_tokens": 128, "new_tokens": 32}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def trace():
    return tracing.load_json(DATA)


def test_busy_and_idle(trace):
    devices, host = trace
    (w0, w1), = [(s, e) for n, s, e in host if n == "bench.window"]
    # by hand: sort the runs and sweep
    busy, end = 0.0, w0
    for s, e in sorted((s, e) for _, s, e in devices[0]):
        s, e = max(s, end), min(e, w1)
        if e > s:
            busy += e - s
            end = e
    out = tracing.reduce(devices, host, MODEL, MIX, PEAK)
    assert out["busy_s"] == pytest.approx(busy / 1e9, rel=1e-12)
    assert out["busy_s"] == pytest.approx(0.246398, abs=1e-6)
    assert out["window_s"] == pytest.approx(3.947778, abs=1e-6)


def test_executables_found_and_timed(trace):
    devices, host = trace
    out = tracing.reduce(devices, host, MODEL, MIX, PEAK)
    tb, step = out["kernels"]["tblock"], out["kernels"]["decode_step"]
    runs = {}
    for name, s, e in devices[0]:
        runs.setdefault(name, []).append((e - s) / 1e9)
    assert tb["runs"] == 32 and len(runs[tb["name"]]) == 32
    assert tb["device_s"] == pytest.approx(sum(runs[tb["name"]]))
    assert tb["device_s"] == pytest.approx(0.001031, abs=1e-6)
    # 128 prompt tokens and the first token replayed, then 30 decode ticks
    assert step["runs"] == 159
    assert step["device_s"] == pytest.approx(0.238732, abs=1e-6)
    assert tb["least_s"] == pytest.approx(
        32 * flops.least_time(flops.tblock(MODEL, 128), PEAK))
    assert step["least_s"] == pytest.approx(sum(
        flops.least_time(flops.decode_step(MODEL, p), PEAK)
        for p in range(159)))
    assert tb["least_s"] < tb["device_s"]
    assert step["least_s"] < step["device_s"]


def test_breakdown_lists_at_most_ten_each(trace):
    out = tracing.reduce(*trace, MODEL, MIX, PEAK)
    ops, gaps = out["breakdown"]["device_ops"], out["breakdown"]["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert ops[0][0].startswith("decode_step ")
    assert [g for _, g in gaps] == sorted((g for _, g in gaps), reverse=True)
    assert gaps[0][0].startswith("bench.prefill")


def test_a_trace_without_the_kernels_leaves_them_out(trace):
    devices, host = trace
    host = [h for h in host if h[0] == "bench.window"]
    out = tracing.reduce(devices, host, MODEL, MIX, PEAK)
    assert out["kernels"] == {}
    assert out["busy_s"] > 0
