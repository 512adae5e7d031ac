"""The device trace of a run's first requests, and its reduction to numbers.

``Tracer`` records a JAX profiler trace (the Python tracer off, host
TraceMe events on) around the requests it is given. ``reduce`` reads the
``.xplane.pb`` it wrote:

* the device's busy time is the union of the intervals of its
  ``XLA Modules`` events inside the traced window (the host annotation
  ``bench.window``), averaged over the chips used;
* the phases of each request are the host annotations ``bench.prefill``
  and ``bench.decode`` (``client.py``); device events are attributed to the
  phase whose interval holds their start;
* the program gives its executables no names of their own (each is a
  ``jit__lambda`` with a fingerprint), so they are told apart by what the
  algorithm fixes: the tblock executable is the one that runs once per
  layer in every prefill, the decode step one that runs at least once per
  decoded token in every decode phase; of several such, the one with the
  most device time.

Events carry nanosecond times on one clock for host and device planes.
"""
from __future__ import annotations

import glob
import os
import shutil
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import flops
from spans import clip, merge

WINDOW, PREFILL, DECODE = "bench.window", "bench.prefill", "bench.decode"
TOP = 10


class Tracer:
    def __init__(self, log_dir: Path):
        self.dir = Path(log_dir)
        self._ann = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(WINDOW)
        self._ann.__enter__()

    def stop(self) -> Path:
        import jax

        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        found = glob.glob(str(self.dir / "**" / "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        return Path(max(found, key=os.path.getmtime))

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def load(path: Path):
    """(device module events per chip, host events) of one trace:
    ``[[(name, start_ns, end_ns)]]`` and ``[(name, start_ns, end_ns)]``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    devices.append([(e.name, e.start_ns, e.end_ns)
                                    for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events)
    return devices, host


def save(xplane: Path, out: Path) -> None:
    """Keep a trace in the compact form ``load_json`` reads: the device
    executables and the benchmark's own host marks."""
    import json

    devices, host = load(xplane)
    host = [h for h in host if h[0].startswith("bench.")]
    Path(out).write_text(json.dumps({"devices": devices, "host": host}))


def load_json(path: Path):
    import json

    d = json.loads(Path(path).read_text())
    return ([[tuple(e) for e in mods] for mods in d["devices"]],
            [tuple(e) for e in d["host"]])


def _spans(host, name) -> List[Tuple[float, float]]:
    return sorted((s, e) for n, s, e in host if n == name)


def _runs_by_phase(mods, phases) -> List[Dict[str, List[float]]]:
    """Per phase interval: {executable: [device seconds of each run]}."""
    out = [defaultdict(list) for _ in phases]
    for name, s, e in mods:
        for i, (ps, pe) in enumerate(phases):
            if ps <= s < pe:
                out[i][name].append((e - s) / 1e9)
                break
    return out


def _pick(per_phase, want) -> Optional[str]:
    """Of the executables whose number of runs ``want`` accepts in every
    phase, the one with the most device time."""
    names = set.intersection(*[set(p) for p in per_phase]) if per_phase \
        else set()
    ok = [n for n in names if all(want(len(p[n])) for p in per_phase)]
    return max(ok, key=lambda n: sum(sum(p[n]) for p in per_phase)) \
        if ok else None


def reduce(devices, host, model: dict, mix: dict, peak: dict) -> dict:
    win = _spans(host, WINDOW)
    if not win or not devices:
        raise RuntimeError("the trace holds no traced window or no device")
    w0, w1 = win[0]
    busy = []
    for mods in devices:
        busy.append(sum(e - s for s, e in merge(
            clip([(s, e) for _, s, e in mods], w0, w1))) / 1e9)
    mods = devices[0]
    prefill = _runs_by_phase(mods, _spans(host, PREFILL))
    decode = _runs_by_phase(mods, _spans(host, DECODE))
    S, N = mix["prompt_tokens"], mix["new_tokens"]

    tblock = _pick(prefill, lambda n: n == model["layers"])
    step = _pick(decode, lambda n: n >= N - 2)
    out = {"busy_s": sum(busy) / len(busy), "window_s": (w1 - w0) / 1e9,
           "kernels": {}}
    if tblock:
        times = [t for p in prefill for t in p[tblock]]
        least = len(times) * flops.least_time(flops.tblock(model, S), peak)
        out["kernels"]["tblock"] = {"name": tblock, "runs": len(times),
                                    "device_s": sum(times),
                                    "least_s": least}
    if step:
        times = [t for p in decode for t in p[step]]
        least = sum(flops.decode_least_time(model, range(len(p[step])), peak)
                    for p in decode)
        out["kernels"]["decode_step"] = {"name": step, "runs": len(times),
                                         "device_s": sum(times),
                                         "least_s": least}
    out["breakdown"] = _breakdown(mods, host, (w0, w1), out["kernels"])
    return out


def _breakdown(mods, host, window, kernels) -> dict:
    """The executables that took most device time, and the longest idle
    gaps of the device, each named by the phase and by the host activity
    that overlapped it most."""
    label = {k["name"]: f"{role} {k['name']}" for role, k in kernels.items()}
    total = defaultdict(float)
    for name, s, e in mods:
        total[label.get(name, name)] += (e - s) / 1e9
    ops = sorted(total.items(), key=lambda kv: -kv[1])[:TOP]

    w0, w1 = window
    busy = merge(clip([(s, e) for _, s, e in mods], w0, w1))
    gaps, cur = [], w0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < w1:
        gaps.append((cur, w1))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    phases = [(n, s, e) for n, s, e in host if n in (PREFILL, DECODE)]
    other = [(n, s, e) for n, s, e in host
             if not n.startswith("bench.") and e - s > 0]
    idle = []
    for gs, ge in gaps:
        def best(events):
            cover = defaultdict(float)
            for n, s, e in events:
                o = min(e, ge) - max(s, gs)
                if o > 0:
                    cover[n] += o
            return max(cover, key=cover.get) if cover else "nothing traced"
        idle.append([f"{best(phases)}: {best(other)}", (ge - gs) / 1e9])
    return {"device_ops": [[n, s] for n, s in ops], "idle_gaps": idle}
