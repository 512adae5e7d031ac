#!/usr/bin/env python3
"""Run one cell of the cold-inference benchmark on the chip it starts on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json``'s ``workloads``) names a model configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``). One run:

1. set-up (timed as ``setup_s``): weights from ``--seed`` on the device,
   drawn by the configuration's model family
   (``bench/families/<family>.py``); the program's cold graph
   (``build_llm_graph``), a ``ColdServer`` whose model store lies on a
   disk filesystem outside the checkout, its offline
   ``decide()`` at the mix's prompt shape, and the mix's
   ``warmup_requests`` whole cold requests: the first warms every shape
   the window uses, the rest the process's host memory;
2. the window: one client in a closed loop for ``--seconds``. Before each
   request the model is evicted from device memory (its files stay in the
   page cache: the only mix, ``page_cache: warm``); the request then runs
   ``cold_start_llm`` to its last token;
3. the check: once the window has closed, the device peak read and the
   program's state freed, a float32 reference of the same seeded weights
   (``bench/refs/<reference>.py``) reads the logits at every served
   position of a seeded sample of the finished requests; ``correct`` holds
   when no served token lies further below the reference's best logit than
   the configuration's limit, no request staged a weight in a narrower
   type than the configuration's ``torch_dtype``, and no request failed.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from the program's spans and
from a profiler trace of the window's first two requests. Every metric
is read by ``bench/metrics/<name>.py``. The last line of standard output
is the result as one JSON object. Without a TPU, or with fewer chips than the
cell asks for, the run prints no result and exits non-zero.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
PLATFORM = "tpu"
MODEL = "model"
N_LITTLE = 3
MAX_PREPS = 2
STORE = "nnv12-bench-store"
# a traced run traces the window's first requests only: one request's
# trace is some 20 MB and takes seconds to read back
TRACED_REQUESTS = 2
# JAX's persistent compilation cache and the profile DB stay in the
# checkout, at fixed paths, so only a checkout's first run compiles and
# profiles (the paper's decision stage is offline)
CACHE_DIR = ".jax_cache"
STATE_DIR = ".bench_state"

sys.path.insert(0, str(BENCH))


def say(msg: str) -> None:
    print(f"[{time.perf_counter() - T_START:8.2f}s] {msg}", file=sys.stderr,
          flush=True)


def memory() -> str:
    """Host resident set, the host's available and page-cache memory, and
    device bytes in use, for the log."""
    rss = 0
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                rss = int(line.split()[1]) * 1024
    host = {}
    with open("/proc/meminfo") as f:
        for line in f:
            key, _, rest = line.partition(":")
            if key in ("MemAvailable", "Cached", "Dirty"):
                host[key] = int(rest.split()[0]) * 1024
    dev = 0
    if "jax" in sys.modules:
        import jax

        dev = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use", 0)
    return (f"host {rss / 1e9:.2f} GB (available "
            f"{host.get('MemAvailable', 0) / 1e9:.2f}, cached "
            f"{host.get('Cached', 0) / 1e9:.2f}, dirty "
            f"{host.get('Dirty', 0) / 1e9:.2f}), device {dev / 1e9:.2f} GB")


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: Path = ROOT):
    """(benchmark, cell, config entry, model dict, mix dict) of ``name``."""
    import modelcfg

    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    model = modelcfg.model(root / conf["file"], conf["name"], root)
    mix = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    if mix["page_cache"] != "warm":
        raise SystemExit(f"mix {cell['traffic']!r}: page_cache "
                         f"{mix['page_cache']!r} is not supported, only "
                         "'warm'")
    return bench, cell, conf, model, mix


def metrics_for(bench: dict, cell: dict, trace: bool):
    """[(name, unit)] the cell reports: its end-to-end metrics, or with a
    trace its per-layer ones."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [(m["name"], m["unit"]) for m in group
            if cell["name"] in m.get("workloads", [cell["name"]])]


def store_base(root: Path = ROOT) -> Path:
    """A directory on a disk filesystem (never tmpfs, whose files cannot
    leave memory) for the run's model store: ``$TMPDIR``, ``$HOME``, else
    the checkout."""
    import filesystem

    for v in (os.environ.get("TMPDIR"), os.environ.get("HOME")):
        if v and os.path.isdir(v) and os.access(v, os.W_OK) \
                and filesystem.on_disk(Path(v)):
            return Path(v) / STORE
    return root / ".bench_run" / STORE


def prompt(seed: int, index: int, mix: dict, vocab: int):
    """Request ``index``'s prompt (negative indices warm up): token ids
    drawn uniformly, every prompt of the mix's one length."""
    import numpy as np

    rng = np.random.default_rng([seed, 1, index + 1] if index >= 0
                                else [seed, 2, -index])
    return rng.integers(0, vocab, mix["prompt_tokens"]).astype(np.int32)


class Cell:
    """The system under test, set up for one cell, and its requests."""

    def __init__(self, model: dict, mix: dict, seed: int, base: Path,
                 state: Path, root: Path = ROOT, **engine_kw):
        self.model, self.mix, self.seed, self.base = model, mix, seed, base
        self.state, self.root = state, root
        self.engine_kw = engine_kw
        self.phases = {}

    def phase(self, name: str, t0: float) -> None:
        self.phases[name] = time.perf_counter() - t0
        say(f"set-up {name}: {self.phases[name]:.4f}s; {memory()}")

    def set_up(self) -> None:
        import jax

        import modelcfg
        from repro.core.llm_graph import build_llm_graph
        from repro.executor.server import ColdServer

        family = modelcfg.family(self.model, self.root)
        self.cfg = family.arch_config(self.model)
        shutil.rmtree(self.base, ignore_errors=True)
        t = time.perf_counter()
        params = family.make(self.seed, self.model)
        jax.block_until_ready(params)
        self.phase("weights", t)
        t = time.perf_counter()
        graph, _ = build_llm_graph(self.cfg, params)
        del params
        self.phase("graph", t)
        t = time.perf_counter()
        root = self.base / "server"
        root.mkdir(parents=True)
        db = self.state / "profile_db.json"
        if db.exists():
            shutil.copyfile(db, root / "profile_db.json")
        self.server = ColdServer(root, n_little=N_LITTLE,
                                 max_concurrent_preps=MAX_PREPS)
        self.engine = self.server.add_model(MODEL, graph, **self.engine_kw)
        del graph
        self.phase("store", t)
        t = time.perf_counter()
        x = prompt(self.seed, -1, self.mix, self.model["vocab"])[None]
        stats = self.server.decide(MODEL, x)
        if stats.get("degraded"):
            raise RuntimeError(f"decide() degraded: {stats.get('error')}")
        self.state.mkdir(parents=True, exist_ok=True)
        tmp = self.state / "profile_db.json.tmp"
        shutil.copyfile(root / "profile_db.json", tmp)
        os.replace(tmp, db)
        self.phase("decide", t)
        say(f"plan: {sorted(set(map(tuple, stats['choices'].values())))}, "
            f"{stats['profile_calls']} profile calls, "
            f"{stats['profile_db_hits']} profile DB hits, read interference "
            f"{stats['io_interference']:.4f}, read depth "
            f"{stats['read_depth']}, estimated makespan "
            f"{stats['est_makespan_s']:.4f}s")
        # the first request compiles or loads every program the window
        # uses; the first cold requests of a process run slower still
        # while its host memory grows, so set-up serves the mix's
        # ``warmup_requests`` before the window opens
        t = time.perf_counter()
        for k in range(self.mix.get("warmup_requests", 1)):
            warm = self.request(-1 - k)
            say(f"warm-up request {k}: first token "
                f"{warm['first'] - warm['issue']:.4f}s, second "
                f"{warm['tokens'][1] - warm['first']:.4f}s later, "
                f"{len(warm['tokens'])} tokens; {memory()}")
        self.phase("warm-up requests", t)

    def request(self, index: int, annotate: bool = False) -> dict:
        """Evict, then serve one prompt to its last token; returns the
        client-side record."""
        from client import Client, token_clock
        from repro.executor.llm_bridge import cold_start_llm

        self.server.evict(MODEL)
        if MODEL in self.server.resident_models():
            raise RuntimeError("the model is still resident after evict()")
        # the bridge leaves each cold start's weights in reference cycles;
        # collected here, outside the timed request, they neither pile up
        # on the chip nor free themselves in the middle of a later request
        gc.collect()
        p = prompt(self.seed, index, self.mix, self.model["vocab"])
        client = Client(self.server, annotate=annotate)
        picks = []
        with token_clock(picks):
            client.mark("bench.prefill")
            issue = time.perf_counter()
            try:
                out = cold_start_llm(
                    self.engine, self.cfg, p,
                    max_new_tokens=self.mix["new_tokens"],
                    n_little=N_LITTLE, server=client, model_name=MODEL)
            finally:
                end = time.perf_counter()
                client.end_mark()
        t0 = client.job_t0
        spans = {}
        for tr in out.run.traces:
            spans.setdefault(tr.kind, []).append((t0 + tr.start,
                                                  t0 + tr.end))
        return {"index": index, "prompt": p, "served": list(out.tokens),
                "issue": issue, "first": client.first_token,
                "tokens": [client.first_token] + picks, "end": end,
                "decode_start": client.decode_start, "spans": spans,
                "staged_below": client.staged_below(self.model["dtype"])}

    def close(self) -> None:
        """Free the program's state: staged weights, executables' inputs,
        the store on disk."""
        self.server.evict(MODEL)
        del self.engine, self.server
        gc.collect()
        shutil.rmtree(self.base, ignore_errors=True)


def widest_gap(model: dict, mix: dict, seed: int, reqs: list,
               root: Path = ROOT) -> dict:
    """The widest gap by which a served token's reference logit lies below
    the reference's best, over a seeded sample of the finished requests."""
    import numpy as np

    import compare
    import modelcfg

    n = mix["compare_requests"]
    rng = np.random.default_rng([seed, 3])
    pick = sorted(rng.choice(len(reqs), size=min(n, len(reqs)),
                             replace=False).tolist())
    sample = [reqs[i] for i in pick]
    ref = load_module(root / "bench" / "refs" / f"{model['reference']}.py")
    params = modelcfg.family(model, root).make(seed, model)
    gap = compare.widest_gap(ref, params, model,
                             [r["prompt"] for r in sample],
                             [r["served"] for r in sample])
    del params
    return {"max_gap": gap, "requests": len(sample),
            "tokens": sum(len(r["served"]) for r in sample)}


def verdict(model: dict, mix: dict, seed: int, reqs: list, failed: int,
            root: Path = ROOT):
    """(correct, checks): each number compared, with its limit."""
    t = time.perf_counter()
    gap = widest_gap(model, mix, seed, reqs, root)
    say(f"reference check over {gap['requests']} requests, "
        f"{gap['tokens']} served tokens: {time.perf_counter() - t:.2f}s")
    checks = {
        "max_gap": {"value": gap["max_gap"],
                    "limit": model["limits"]["max_gap"]},
        "staged_below_dtype_bytes": {
            "value": sum(r["staged_below"] for r in reqs), "limit": 0},
        "failed_requests": {"value": failed, "limit": 0},
    }
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        root: Path = ROOT, require: str = PLATFORM) -> dict:
    import resource

    import compiles
    import device
    import filesystem
    import modelcfg
    import tracing

    bench, cell, conf, model, mix = load_cell(cell_name, root)
    family = modelcfg.family(model, root)
    devs = device.require(require, cell["chips"])
    peak = device.peak(devs[0].device_kind, root / "bench" / "peaks.json")
    say(f"cell {cell_name}: {conf['name']} x {cell['traffic']}, seed {seed}, "
        f"{seconds}s, trace {int(trace)}; devices {len(devs)} x "
        f"{devs[0].device_kind}")
    counter = compiles.CompileCounter()
    base = store_base(root)
    fs, mnt = filesystem.filesystem_of(base.parent)
    say(f"model store at {base} on {fs} ({mnt}); page cache "
        f"{mix['page_cache']}")
    c = Cell(model, mix, seed, base, root / STATE_DIR, root)
    c.set_up()
    setup_s = time.perf_counter() - T_START
    say(f"set-up {setup_s:.4f}s; {counter.compiled} backend compiles "
        f"({counter.seconds:.2f}s), {counter.hits} cache loads")

    tracer = tracing.Tracer(base.parent / f"{STORE}-trace") if trace \
        else None
    started = False
    compiled0 = counter.compiled
    reqs, failed, errors, xplane = [], 0, [], None
    w0 = time.perf_counter()
    i = 0
    while time.perf_counter() - w0 < seconds:
        traced = tracer is not None and i < TRACED_REQUESTS
        if traced and not started:
            tracer.start()
            started = True
        try:
            r = c.request(i, annotate=traced)
        except Exception as e:  # a failed request counts, and is shown
            import traceback

            traceback.print_exc()
            failed += 1
            errors.append(repr(e))
            say(f"request {i} failed: {e!r}")
        else:
            if len(r["served"]) != mix["new_tokens"]:
                failed += 1
                errors.append(f"request {i}: {len(r['served'])} tokens")
            reqs.append(r)
            say(f"request {i}: first token {r['first'] - r['issue']:.4f}s, "
                f"second {r['tokens'][1] - r['first']:.4f}s later, last "
                f"{r['end'] - r['issue']:.4f}s; {memory()}")
        i += 1
        if traced and i == TRACED_REQUESTS:
            xplane = tracer.stop()
    if started and xplane is None:
        xplane = tracer.stop()
    w1 = time.perf_counter()
    in_window = counter.compiled - compiled0
    devinfo = device.describe(devs)
    c.close()
    if not reqs:
        raise RuntimeError(f"no request finished in the window: {errors}")
    say(f"window {w1 - w0:.4f}s, {i} requests, {in_window} compiles in "
        f"it; device peak {devinfo['memory_peak_bytes']} bytes; host peak "
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024} bytes")
    correct, checks = verdict(model, mix, seed, reqs, failed, root)

    reduced = None
    if xplane is not None:
        t = time.perf_counter()
        reduced = tracing.reduce(*tracing.load(xplane),
                                 family.roles(model, mix, peak))
        tracer.close()
        say(f"trace of {min(i, TRACED_REQUESTS)} requests read in "
            f"{time.perf_counter() - t:.2f}s: busy {reduced['busy_s']:.4f}s "
            f"of {reduced['window_s']:.4f}s; {reduced['kernels']}")
    rec = {"model": model, "family": family, "mix": mix, "requests": reqs,
           "setup_s": setup_s, "compiled_in_window": in_window,
           "trace": reduced, "peak": peak}
    metrics = {}
    for name, unit in metrics_for(bench, cell, trace):
        v = load_module(root / "bench" / "metrics" / f"{name}.py").read(rec)
        if v is not None:
            metrics[name] = {"value": v, "unit": unit}
    if reduced:
        devinfo["busy_s"] = reduced["busy_s"]
        devinfo["window_s"] = reduced["window_s"]
    out = {"correct": correct,
           "attempted": i, "failed": failed, "metrics": metrics,
           "device": devinfo}
    if reduced:
        out["breakdown"] = reduced["breakdown"]
    out["checks"] = checks
    return out


def setup_jax(root: Path = ROOT) -> None:
    """The program on the path, and JAX's persistent compilation cache in
    the checkout (before JAX is first imported)."""
    cache = str(root / CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    sys.path.insert(0, str(root / "src"))
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    # every executable goes to the persistent cache, however quickly it
    # compiled, so a later run loads it instead of compiling it again; the
    # cache holds this checkout's programs only and is never trimmed
    # (trimming reads every entry's access time on each write)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        setup_jax()
        out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except Exception as e:
        import traceback

        traceback.print_exc()
        print(f"benchmark run failed: {e!r}", file=sys.stderr)
        return 1
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
