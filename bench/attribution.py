"""Device idle time put down to the program's own host spans.

``idle_attributed`` reads a trace as ``tracing.load`` returns it (device
``XLA Modules`` events per chip, and host events) with the program's
``nnv12.*`` annotations kept. A traced request runs from the start of its
``bench.prefill`` mark to the end of its ``bench.decode`` mark; inside it,
the device is idle where no executable of the first chip runs. The share
of that idle time that lies under at least one ``nnv12.`` host event is
what the program's spans can explain; every other idle gap is named by
the host events over it.

Not read by a metric yet: ``tracing.reduce`` and ``run.py`` keep neither
the host events nor the trace once a run has reduced it. Until they do,
this module's command makes one traced run of a cell and reads its trace
before the run drops it:

    python3 bench/attribution.py --workload <cell> --seed <n> --seconds <s> --out <dir>

It prints the run's result line, as ``run.py --trace 1`` would, and
writes ``<dir>/attribution.json`` (``idle_attributed`` over the traced
requests, and the count and seconds of each kind of ``nnv12.`` event in
them) and ``<dir>/trace_1req_spans.json``: the first traced request in the
form ``tracing.load_json`` reads, with the ``nnv12.`` events kept
(``tests/data/trace_smollm_1req_spans.json`` is such a file).
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from pathlib import Path
from typing import List, Tuple

from spans import clip, merge

PROGRAM = "nnv12."
WINDOW, PREFILL, DECODE = "bench.window", "bench.prefill", "bench.decode"


def _overlap(a: List[Tuple[float, float]], b: List[Tuple[float, float]]
             ) -> float:
    """Length of the intersection of two merged, sorted interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _gaps(busy: List[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """The parts of [lo, hi] outside the merged intervals ``busy``."""
    out, cur = [], lo
    for s, e in clip(busy, lo, hi):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def idle_attributed(devices, host, longest: float = 0.01) -> dict:
    """``{"idle_s", "attributed_s", "idle_attributed"}`` over the traced
    requests (seconds and %), and ``uncovered``: each idle stretch of more
    than ``longest`` seconds that no program span covers, as [request,
    offset from the request's start in s, length in s, the host event that
    covers most of it]."""
    requests = list(zip(sorted((s, e) for n, s, e in host if n == PREFILL),
                        sorted((s, e) for n, s, e in host if n == DECODE)))
    busy = merge((s, e) for _, s, e in devices[0])
    program = merge((s, e) for n, s, e in host if n.startswith(PROGRAM))
    other = [(n, s, e) for n, s, e in host
             if not n.startswith(PROGRAM) and not n.startswith("bench.")]
    idle = attributed = 0.0
    uncovered = []
    for k, ((r0, _), (_, r1)) in enumerate(requests):
        gaps = _gaps(busy, r0, r1)
        idle += sum(e - s for s, e in gaps)
        attributed += _overlap(gaps, program)
        for s, e in _gaps(program, r0, r1):
            for gs, ge in clip(gaps, s, e):
                if ge - gs > longest * 1e9:
                    cover = {}
                    for n, hs, he in other:
                        o = min(he, ge) - max(hs, gs)
                        if o > 0:
                            cover[n] = cover.get(n, 0) + o
                    uncovered.append([k, (gs - r0) / 1e9, (ge - gs) / 1e9,
                                      max(cover, key=cover.get)
                                      if cover else "nothing traced"])
    return {"idle_s": idle / 1e9, "attributed_s": attributed / 1e9,
            "idle_attributed": 100.0 * attributed / idle if idle else None,
            "uncovered": uncovered}


def _requests(host) -> List[Tuple[float, float]]:
    return list(zip(sorted(s for n, s, _ in host if n == PREFILL),
                    sorted(e for n, _, e in host if n == DECODE)))


def cut(devices, host, k: int = 0) -> dict:
    """The ``k``-th traced request: the device executables that start in
    it and the benchmark's and program's host events clipped to it (the
    window becomes the request), in ns from the start of the window."""
    origin = min(s for n, s, _ in host if n == WINDOW)
    r0, r1 = _requests(host)[k]
    return {
        "devices": [[(n, s - origin, e - origin) for n, s, e in mods
                     if r0 <= s < r1] for mods in devices],
        "host": [(n, max(s, r0) - origin, min(e, r1) - origin)
                 for n, s, e in host
                 if n.startswith(("bench.", PROGRAM)) and e > r0 and s < r1]}


def by_kind(host) -> dict:
    """Per ``nnv12.`` event name, its count and seconds inside the traced
    requests."""
    out = defaultdict(lambda: [0, 0.0])
    for r0, r1 in _requests(host):
        for n, s, e in host:
            if n.startswith(PROGRAM) and r0 <= s < r1:
                out[n][0] += 1
                out[n][1] += (e - s) / 1e9
    return {n: {"count": c, "seconds": t} for n, (c, t) in sorted(out.items())}


def main(argv=None) -> int:
    import run
    import tracing

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args(argv)
    loaded = []
    load = tracing.load

    def keep(path):
        loaded.append(load(path))
        return loaded[-1]

    tracing.load = keep
    run.setup_jax()
    out = run.run(args.workload, args.seed, args.seconds, True)
    print(json.dumps(out))
    if not loaded:
        print("the run recorded no trace", file=sys.stderr)
        return 1
    devices, host = loaded[0]
    args.out.mkdir(parents=True, exist_ok=True)
    (args.out / "attribution.json").write_text(json.dumps(
        {**idle_attributed(devices, host), "events": by_kind(host)},
        indent=1))
    (args.out / "trace_1req_spans.json").write_text(
        json.dumps(cut(devices, host)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
