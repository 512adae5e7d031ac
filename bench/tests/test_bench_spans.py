"""The readers of the program's own spans, on hand-built records, and the
attribution of device idle time to those spans.

``data/trace_smollm_1req_spans.json`` holds one cold smollm-360m request
recorded on a TPU v5e and cut by ``bench/attribution.py`` (``python3
bench/attribution.py --workload smollm-360m.cold_host --seed 3713000023
--seconds 51 --out <dir>``, its ``trace_1req_spans.json``): the device
executables (``XLA Modules``), the harness's marks (the window cut to the
request) and the program's ``nnv12.*`` host events."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import attribution  # noqa: E402
import modelcfg  # noqa: E402
import run  # noqa: E402
import tracing  # noqa: E402

DATA = BENCH / "tests" / "data"
SPANS = DATA / "trace_smollm_1req_spans.json"


def reader(name):
    return run.load_module(BENCH / "metrics" / f"{name}.py").read


def _request(issue, picks, spans):
    return {"issue": issue, "first": picks[0], "tokens": list(picks),
            "end": picks[-1], "decode_start": None,
            "spans": {"first_token": [(issue + 1.0, issue + 1.1)], **spans}}


# two requests issued at 0 and 10 s, their last tokens at 5 and 16 s
RECORD = {"requests": [
    _request(0.0, [2.0, 3.0, 4.0, 5.0], {
        "handoff.copy": [(2.0, 2.5)],
        "handoff.stack": [(2.5, 2.6)],
        "handoff.replay": [(2.6, 3.0)],
        "compile": [(1.0, 1.2), (1.1, 1.3), (2.7, 2.8)],
        "decode.pick": [(3.0, 3.002), (4.0, 4.004)],
        "decode.wait": [(3.0005, 3.0015), (4.001, 4.003)],
        "gc": [(-1.0, 0.5), (4.5, 4.6), (5.5, 6.0)]}),
    _request(10.0, [12.0, 13.0, 16.0], {
        "handoff.copy": [(12.0, 12.7)],
        "handoff.stack": [(12.7, 12.9)],
        "handoff.replay": [(12.9, 13.0)],
        "decode.pick": [(13.0, 13.003)]})]}

EXPECTED = {
    "handoff_copy_s": (0.5 + 0.7) / 2,
    "handoff_stack_s": (0.1 + 0.2) / 2,
    "replay_busy_s": (0.4 + 0.1) / 2,
    # the union of the first request's compiles, none in the second
    "request_compile_s": (0.3 + 0.1) / 2,
    # each pick less the wait inside it
    "pick_ms": 1000 * (0.001 + 0.002 + 0.003) / 3,
    # only what lies between issue and last token counts
    "gc_pause_s": (0.5 + 0.1) / 2,
}


@pytest.mark.parametrize("name", list(EXPECTED))
def test_span_readers_on_a_known_record(name):
    assert reader(name)(RECORD) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", list(EXPECTED))
def test_span_readers_are_silent_without_the_programs_spans(name):
    """A program that records none of these spans (the request has its
    ``read``/``stage`` spans only) gives no reading, and no error."""
    rec = {"requests": [
        {"issue": 0.0, "first": 1.0, "tokens": [1.0, 2.0, 3.0], "end": 3.0,
         "decode_start": 1.5, "spans": {"read": [(0, 1)],
                                        "stage": [(0.5, 1)]}}]}
    assert reader(name)(rec) is None


def _ns(*spans):
    return [(n, int(s * 1e9), int(e * 1e9)) for n, s, e in spans]


def test_idle_attributed_on_a_known_trace():
    # one request from 0 to 10 s; the device runs 1-2 and 6-7 s, so it is
    # idle for 8 s; program spans cover 0-1.5 and 5-6.5 s and an unnamed
    # host event 8-9 s: 1 + 1 s of the idle time is the program's
    devices = [_ns(("jit_tblock_bf16_cast", 1, 2), ("jit_decode_step", 6, 7))]
    host = _ns(("bench.prefill", 0, 4), ("bench.decode", 4, 10),
               ("nnv12.stage", 0, 1.5), ("nnv12.decode.pick", 5, 6.5),
               ("_Transpose", 8, 9))
    out = attribution.idle_attributed(devices, host, longest=1.5)
    assert out["idle_s"] == pytest.approx(8.0)
    assert out["attributed_s"] == pytest.approx(2.0)
    assert out["idle_attributed"] == pytest.approx(25.0)
    # idle and uncovered for more than 1.5 s: 2-5 s and 7-10 s
    assert out["uncovered"] == [[0, 2.0, 3.0, "nothing traced"],
                                [0, 7.0, 3.0, "_Transpose"]]


def test_a_trace_without_program_spans_attributes_nothing():
    """The trace recorded before the program wrote its own spans."""
    devices, host = tracing.load_json(DATA / "trace_smollm_1req.json")
    out = attribution.idle_attributed(devices, host)
    assert out["idle_s"] > 0 and out["attributed_s"] == 0
    assert out["idle_attributed"] == 0


def _by_sweep(devices, host):
    """Idle and attributed seconds by brute force: every stretch between
    two consecutive event boundaries inside the request, tested at its
    middle."""
    (r0, _), = [(s, e) for n, s, e in host if n == "bench.prefill"]
    (_, r1), = [(s, e) for n, s, e in host if n == "bench.decode"]
    dev = [(s, e) for _, s, e in devices[0]]
    prog = [(s, e) for n, s, e in host if n.startswith("nnv12.")]
    cuts = sorted({r0, r1} | {t for s, e in dev + prog for t in (s, e)
                              if r0 < t < r1})
    idle = attributed = 0.0
    for a, b in zip(cuts, cuts[1:]):
        m = (a + b) / 2
        if not any(s <= m < e for s, e in dev):
            idle += b - a
            if any(s <= m < e for s, e in prog):
                attributed += b - a
    return idle / 1e9, attributed / 1e9


def test_idle_attributed_on_a_recorded_request():
    devices, host = tracing.load_json(SPANS)
    out = attribution.idle_attributed(devices, host)
    idle, attributed = _by_sweep(devices, host)
    assert out["idle_s"] == pytest.approx(idle, rel=1e-9)
    assert out["attributed_s"] == pytest.approx(attributed, rel=1e-9)
    assert out["idle_attributed"] == pytest.approx(94.555129, abs=1e-5)
    # the one stretch over 10 ms the program's spans leave out: from the
    # request's issue to its first task
    (k, offset, length, _), = out["uncovered"]
    assert (k, offset) == (0, 0.0) and length == pytest.approx(0.09987,
                                                               abs=1e-5)


FIXTURES = {
    # file: (tblock name, device seconds of the tblock and of the step)
    "trace_smollm_1req.json": ("jit__lambda(", 0.001031, 0.238732),
    "trace_smollm_1req_spans.json": ("jit_tblock_bf16_cast(", 0.001035,
                                     0.238772),
}


@pytest.mark.parametrize("name", list(FIXTURES))
def test_reduce_finds_the_kernels_in_either_trace(name):
    """The reduction reads the trace recorded before the program named its
    executables as before, and the one recorded after by the new names."""
    devices, host = tracing.load_json(DATA / name)
    model = modelcfg.model(BENCH / "configs" / "smollm-360m.json", "smollm")
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    out = tracing.reduce(devices, host, model,
                         {"prompt_tokens": 128, "new_tokens": 32}, peak)
    prefix, tblock_s, step_s = FIXTURES[name]
    tb, step = out["kernels"]["tblock"], out["kernels"]["decode_step"]
    assert tb["name"].startswith(prefix) and tb["runs"] == 32
    assert step["runs"] == 159
    assert tb["device_s"] == pytest.approx(tblock_s, abs=1e-6)
    assert step["device_s"] == pytest.approx(step_s, abs=1e-6)
    if prefix.startswith("jit_tblock"):
        assert step["name"].startswith("jit_decode_step(")
        gaps = [g for g, _ in out["breakdown"]["idle_gaps"][:2]]
        assert gaps == ["bench.prefill: nnv12.stage",
                        "bench.prefill: nnv12.handoff.copy"]


def test_cut_keeps_one_request_with_the_programs_events():
    """Two traced requests, 0-4 and 5-9 s of a window that opens at 1 s:
    the first is kept, clipped, on a clock from the window's start."""
    devices = [_ns(("jit_tblock_bf16_cast", 2, 3), ("jit_decode_step", 6, 7))]
    host = _ns(("bench.window", 1, 9), ("bench.prefill", 1, 2.5),
               ("bench.decode", 2.5, 4), ("bench.prefill", 5, 6),
               ("bench.decode", 6, 9), ("nnv12.stage", 0.5, 2),
               ("nnv12.decode.pick", 3.5, 4.5), ("_Transpose", 2, 3))
    out = attribution.cut(devices, host)
    assert out["devices"] == [_ns(("jit_tblock_bf16_cast", 1, 2))]
    assert sorted(out["host"]) == sorted(_ns(
        ("bench.window", 0, 3), ("bench.prefill", 0, 1.5),
        ("bench.decode", 1.5, 3), ("nnv12.stage", 0, 1),
        ("nnv12.decode.pick", 2.5, 3)))
    # what the cut keeps is what the attribution of that request reads
    whole = attribution.idle_attributed(devices, host)
    one = attribution.idle_attributed(out["devices"], out["host"])
    assert one["idle_s"] == pytest.approx(2.0)
    assert whole["idle_s"] == pytest.approx(2.0 + 3.0)


def test_events_by_kind_inside_the_traced_requests():
    host = _ns(("bench.prefill", 0, 1), ("bench.decode", 1, 2),
               ("bench.prefill", 5, 6), ("bench.decode", 6, 7),
               ("nnv12.decode.pick", 1.5, 1.75), ("nnv12.decode.pick", 6, 6.5),
               ("nnv12.decode.wait", 6.1, 6.2), ("nnv12.stage", 3, 4))
    assert attribution.by_kind(host) == {
        "nnv12.decode.pick": {"count": 2, "seconds": pytest.approx(0.75)},
        "nnv12.decode.wait": {"count": 1, "seconds": pytest.approx(0.1)}}
