#!/usr/bin/env python3
"""Readings for the limits that decide ``correct``: the program's, and
the controls', on the chip, at a cell's own size.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 \\
        [--requests 12] [--save-trace trace.json]

For each seed it sets the cell up as a benchmark run does and serves
``--requests`` requests as the window would (the mix's
``compare_requests`` by default, as many as a run compares), then sets it
up again with the program's own int8 path switched on (lossy kernels
allowed, int8 the only one beside each layer's reference kernel) and
serves the same prompts. It prints one JSON line per seed:

* ``program``: each number a run compares (``run.verdict``) for the sound
  program, and whether it is correct;
* ``int8_path``: the same for the program's int8 path, and the plan's
  kernels: the control, which has to come out as not correct;
* ``int8``, ``fp8``: the widest gap for the token that the reference put
  one precision step below the stated bfloat16 (every weight matrix
  rounded per output channel) picks first, at the same positions of the
  sound program's prompts and served tokens.

A limit sits between the program's highest reading and the controls'
lowest. ``--save-trace`` keeps the first seed's first two requests'
device trace in the compact form the trace tests read. Benchmark runs
never run this script.
"""
import argparse
import json
import sys
from pathlib import Path

REFERENCE_CONTROLS = ("int8", "fp8")
# the program's own lower-precision path: lossy kernels allowed, int8 the
# only one offered beside each layer's reference kernel
INT8_PATH = {"allow_lossy": True, "kernel_allowlist": ["int8"]}
BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))


def serve(model, mix, seed, requests, root, state, trace_to=None,
          **engine_kw):
    """The cell set up as a run sets it up; ``requests`` requests served;
    the program freed. Returns (requests, the plan's kernels)."""
    import run
    import tracing

    c = run.Cell(model, mix, seed, run.store_base(root), state, **engine_kw)
    c.set_up()
    kernels = sorted({ch.kernel for ch in c.engine.plan.choices})
    tracer = tracing.Tracer(c.base.parent / "control-trace") \
        if trace_to else None
    reqs = []
    for i in range(requests):
        if tracer is not None and i == 0:
            tracer.start()
        reqs.append(c.request(i, annotate=tracer is not None and i < 2))
        if tracer is not None and i == 1:
            tracing.save(tracer.stop(), trace_to)
            tracer.close()
            tracer = None
    c.close()
    return reqs, kernels


def readings(cell_name: str, seed: int, requests: int, trace_to=None,
             root: Path = ROOT, require: str = "tpu") -> dict:
    import numpy as np

    import compare
    import device
    import run
    import weights

    bench, cell, conf, model, mix = run.load_cell(cell_name, root)
    device.require(require, cell["chips"])
    state = root / run.STATE_DIR
    out = {"cell": cell_name, "seed": seed}
    reqs, kernels = serve(model, mix, seed, requests, root, state, trace_to)
    correct, checks = run.verdict(model, mix, seed, reqs, 0, root)
    out["program"] = {"correct": correct, "kernels": kernels,
                      **{k: c["value"] for k, c in checks.items()}}
    low, kernels = serve(model, mix, seed, requests, root, state / "int8",
                         **INT8_PATH)
    correct, checks = run.verdict(model, mix, seed, low, 0, root)
    out["int8_path"] = {"correct": correct, "kernels": kernels,
                        **{k: c["value"] for k, c in checks.items()}}

    ref = run.load_module(root / "bench" / "refs" /
                          f"{model['reference']}.py")
    params = weights.make(seed, model)
    rows, positions = compare.served_rows([r["prompt"] for r in reqs],
                                          [r["served"] for r in reqs])
    exact = np.asarray(ref.logits_at(params, model, rows, positions))
    out["tokens"] = len(reqs) * len(reqs[0]["served"])
    for kind in REFERENCE_CONTROLS:
        lowered = np.asarray(ref.logits_at(params, model, rows, positions,
                                           quantize=kind))
        out[kind] = float(compare.gaps(exact, lowered.argmax(-1)).max())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--requests", type=int, default=0)
    ap.add_argument("--save-trace", type=Path)
    args = ap.parse_args(argv)
    import run

    run.setup_jax()
    n = args.requests or run.load_cell(args.workload)[4]["compare_requests"]
    for k, seed in enumerate(args.seeds):
        out = readings(args.workload, seed, n,
                       args.save_trace if k == 0 else None)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
