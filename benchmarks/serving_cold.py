"""Multi-model cold-serving benchmark — the executor subsystem's CI gate.

Arms:
  * concurrent — two CNN models cold-start at once on ONE persistent
    CorePool through a ColdServer with ``max_concurrent_preps=1``:
    outputs must be bit-equal to each model's isolated cold start, the
    admission gauge must never exceed the cap, and the steady path must
    create zero pool threads after warm-up.
  * cold_llm — a tiny LLM cold start through the serving bridge: the
    first token must be emitted before the last layer's decode-path prep
    completes, with at least one weight-prep op still in flight when the
    exec chain started (execute-as-you-load).
  * quantized_llm — the same cold start on a super-bundle store with
    int4 cache extents eligible (format v4): ``decide()`` must pick the
    quantized entry for a majority of matmul layers, the measured cold
    read bytes must drop >= 2x vs the bf16-cache arm, prefill logits
    must stay correlated, and the first-token-before-last-prep policy
    invariant must survive the quantized path.

``--smoke`` hard-fails on any gate; CI runs it on every push.
"""
from __future__ import annotations

import argparse
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

try:
    from benchmarks.common import csv_line
except ImportError:  # invoked as `python benchmarks/serving_cold.py`
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from benchmarks.common import csv_line
from repro.configs import get_config
from repro.core.llm_graph import tiny_llm_graph
from repro.executor.llm_bridge import cold_start_llm
from repro.executor.server import ColdServer
from repro.models.cnn import build_cnn


def _gate(ok: bool, msg: str, failures: list):
    print(("PASS " if ok else "FAIL ") + msg)
    if not ok:
        failures.append(msg)


def run_concurrent(failures: list, *, image=16, width=0.25):
    root = tempfile.mkdtemp(prefix="nnv12_serving_")
    server = ColdServer(root, n_little=2, max_concurrent_preps=1)
    models = {}
    for name, arch in (("mnet", "mobilenet"), ("snet", "squeezenet")):
        layers, x = build_cnn(arch, image=image, width=width)
        server.add_model(name, layers)
        server.decide(name, x, n_little=2)
        models[name] = x

    # isolated baselines (also warms compile caches so the concurrent arm
    # times pure runtime work)
    isolated = {n: server.cold_start(n, x).result()
                for n, x in models.items()}
    pool = server.pool
    threads_before = pool.threads_created

    results = {}

    def go(name, x):
        results[name] = server.cold_start(name, x).result()

    ts = [threading.Thread(target=go, args=item) for item in models.items()]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = time.perf_counter() - t0

    for name in models:
        diff = float(np.abs(np.asarray(results[name].output)
                            - np.asarray(isolated[name].output)).max())
        _gate(diff == 0.0,
              f"concurrent/{name}: output matches isolated cold start "
              f"(max diff {diff:.1e})", failures)
        own = {t.layer for t in results[name].traces}
        _gate(bool(own) and own == {t.layer for t in isolated[name].traces},
              f"concurrent/{name}: traces cover exactly its own layers "
              f"({len(own)} layers)", failures)
    _gate(server.stats["max_active_preps"] <= 1,
          f"admission: co-running preps {server.stats['max_active_preps']} "
          f"<= cap 1", failures)
    _gate(pool.threads_created == threads_before,
          f"steady path: 0 pool threads created across concurrent runs "
          f"(total {pool.threads_created})", failures)
    print(csv_line("serving/concurrent_2model_wall", wall))
    print(csv_line("serving/isolated_sum_wall",
                   sum(r.total_s for r in isolated.values())))


def run_cold_llm(failures: list, *, num_layers=6):
    cfg = get_config("smollm-360m").reduced(
        num_layers=num_layers, d_model=128, d_ff=256, num_heads=2,
        num_kv_heads=1, head_dim=64, vocab_size=512)
    graph, toks = tiny_llm_graph(num_layers)
    root = tempfile.mkdtemp(prefix="nnv12_coldllm_")
    server = ColdServer(root, n_little=2, max_concurrent_preps=2)
    eng = server.add_model("llm", graph)
    server.decide("llm", toks, n_little=2)
    res = cold_start_llm(eng, cfg, toks[0], max_new_tokens=4, n_little=2,
                         server=server, model_name="llm")
    # the actual overlap evidence: execute-as-you-load
    _gate(res.overlapped_layers >= 1,
          f"cold_llm: {res.overlapped_layers} weight-prep ops still in "
          f"flight when the exec chain started (execute-as-you-load); "
          f"{res.overlapped_packs} decode packs overlapped the chain",
          failures)
    _gate(len(res.tokens) == 4,
          f"cold_llm: decoded {len(res.tokens)} tokens through the "
          f"BatchedServer bridge", failures)
    print(csv_line("serving/cold_llm_first_token", res.first_token_s))
    print(csv_line("serving/cold_llm_decode_ready", res.decode_ready_s))


def run_quantized_llm(failures: list, *, num_layers=6):
    """bf16-cache vs int4-cache cold LLM arms over super-bundle v4.

    Both arms run the full serving bridge (ColdServer -> pipeline ->
    BatchedServer decode); they differ only in which transform kernels
    Algorithm 1 may cache. Byte counts come from the store's real read
    path, so the ratio gate measures on-disk cold traffic, not the plan.
    TTFT is reported for both arms but not hard-gated: at this model
    size wall-clock is compile/jit-dominated and would gate on noise.
    """
    from repro.core.profiler import SyntheticProfiler

    cfg = get_config("smollm-360m").reduced(
        num_layers=num_layers, d_model=128, d_ff=256, num_heads=2,
        num_kv_heads=1, head_dim=64, vocab_size=512)
    arms = {}
    for arm, allow in (("bf16", ["bf16_cast"]),
                       ("int4", ["int4", "bf16_cast"])):
        graph, toks = tiny_llm_graph(num_layers)
        matmul = [l.spec.name for l in graph
                  if l.spec.op_type in ("tblock", "lmhead")]
        root = tempfile.mkdtemp(prefix=f"nnv12_qllm_{arm}_")
        server = ColdServer(root, n_little=2, max_concurrent_preps=2)
        eng = server.add_model("llm", graph, store_fmt="super",
                               allow_lossy=True, kernel_allowlist=allow)
        # deterministic synthetic cost model, no wall-clock interference
        # calibration: the pick/byte gates must not depend on host timings
        eng.profiler_factory = SyntheticProfiler
        server.decide("llm", toks, n_little=2,
                      calibrate_interference=False)
        picked = {l.spec.name: c for l, c in zip(eng.layers,
                                                 eng.plan.choices)}
        n_quant = sum(1 for n in matmul
                      if picked[n].kernel == arm and picked[n].use_cache)
        served0 = eng.store.bytes_served()
        res = cold_start_llm(eng, cfg, toks[0], max_new_tokens=4,
                             n_little=2, server=server, model_name="llm")
        arms[arm] = {
            "cold_bytes": eng.store.bytes_served() - served0,
            "ttft": res.first_token_s,
            "logits": np.asarray(res.run.output, np.float32),
            "n_quant": n_quant, "n_matmul": len(matmul), "res": res,
        }
        # bytes/ratios are not seconds — bypass csv_line's us scaling
        print(f"serving/quantized_llm/{arm}/cold_bytes,"
              f"{arms[arm]['cold_bytes']},")
        print(csv_line(f"serving/quantized_llm/{arm}/first_token",
                       res.first_token_s))

    q = arms["int4"]
    _gate(q["n_quant"] > q["n_matmul"] // 2,
          f"quantized_llm: decide() picked the int4 cache for "
          f"{q['n_quant']}/{q['n_matmul']} matmul layers (majority)",
          failures)
    ratio = arms["bf16"]["cold_bytes"] / max(1, q["cold_bytes"])
    _gate(ratio >= 2.0,
          f"quantized_llm: measured cold read bytes "
          f"{arms['bf16']['cold_bytes']} -> {q['cold_bytes']} "
          f"({ratio:.2f}x >= 2.0x below the bf16 cache)", failures)
    a = arms["bf16"]["logits"].ravel()
    b = q["logits"].ravel()
    corr = float(np.corrcoef(a, b)[0, 1])
    # int4 on every matmul of a 6-block model lands ~0.80; gate well below
    # that so the check catches garbage, not quantization noise
    _gate(corr > 0.75,
          f"quantized_llm: prefill logits correlate with the bf16 arm "
          f"(corr {corr:.4f} > 0.75)", failures)
    print(f"serving/quantized_llm/bytes_ratio,{ratio:.4f},")
    print(f"serving/quantized_llm/ttft_ratio,"
          f"{q['ttft'] / max(1e-9, arms['bf16']['ttft']):.4f},")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes + hard-fail gates (CI)")
    args = ap.parse_args(argv)
    from repro.core.compile_cache import setup_compile_cache

    setup_compile_cache()
    failures: list = []
    run_concurrent(failures)
    run_cold_llm(failures)
    run_quantized_llm(failures)
    if failures:
        print(f"\n{len(failures)} gate(s) failed:", file=sys.stderr)
        for f in failures:
            print(f"  - {f}", file=sys.stderr)
        if args.smoke:
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
