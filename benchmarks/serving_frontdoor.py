"""Front-door chaos + priority benchmark — the supervision tier's CI gate.

Arms:
  * failover — two supervised worker processes; a cold-start request is
    dispatched and its worker is SIGKILLed mid-flight. Gates: the request
    fails over to the sibling and completes within its deadline, the
    output is bit-identical to an isolated one-worker cold start, the
    victim restarts under the exponential-backoff policy and serves
    again, and nothing leaks (no stuck in-flight entries, queues empty).
  * priority — worker slots saturated with batch-lane requests; an
    interactive request must dispatch ahead of the backlog with bounded
    queue delay, and over-deadline requests are shed with typed
    ``DeadlineExceeded`` BEFORE consuming a worker slot (dispatch
    counters unchanged).
  * warm-transfer — two workers on an emulated-slow disk
    (``--sim-disk-bytes-per-s``); w0 cold-starts from disk (the
    no-transfer baseline), then a request pinned to w1 races a peer
    warm-state fetch from w0's RAM against w1's local chains. Gates:
    the race armed and the donor served it, w1's cold start read ≥2×
    fewer local disk bytes than the baseline, the output is
    bit-identical to w0's, and nothing leaked after the race (no I/O
    in flight, no held pinned bytes, no stuck requests).

``--smoke`` hard-fails on any gate; CI runs it on every push.
"""
from __future__ import annotations

import argparse
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

try:
    from benchmarks.common import csv_line  # noqa: F401  (import-path probe)
except ImportError:  # invoked as `python benchmarks/serving_frontdoor.py`
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
from repro.executor.frontdoor import BATCH, INTERACTIVE, FrontDoor
from repro.faults import DeadlineExceeded
from repro.models.cnn import build_cnn

WORKER_ARGS = {"n_little": 2, "n_big": 1}


def _gate(ok: bool, msg: str, failures: list):
    print(("PASS " if ok else "FAIL ") + msg)
    if not ok:
        failures.append(msg)


def isolated_output(root, x, **model_kw) -> np.ndarray:
    """One worker's isolated cold start, served by a one-worker front door:
    the model runs in a worker, never in this process (on a TPU host the
    parent must leave every chip to the workers). A later front door on the
    same ``root`` shares this one's profile DB, hence its plan."""
    with FrontDoor(root, n_workers=1, worker_args=WORKER_ARGS) as fd:
        fd.add_model("mnet", "repro.models.cnn:build_cnn", **model_kw)
        return np.asarray(fd.request("mnet", x).result(120)["output"])


def run_failover(failures: list, *, image=32, width=0.5):
    root = tempfile.mkdtemp(prefix="nnv12_frontdoor_")
    _, x = build_cnn("mobilenet", image=image, width=width)
    ref = isolated_output(root + "/fd", x, name="mobilenet", image=image,
                          width=width)

    fd = FrontDoor(root + "/fd", n_workers=2, worker_args=WORKER_ARGS)
    fd.start()
    try:
        fd.add_model("mnet", "repro.models.cnn:build_cnn",
                     name="mobilenet", image=image, width=width)

        deadline = 120.0
        req = fd.request("mnet", x, deadline_s=deadline)
        for _ in range(1000):        # wait for dispatch so we know the victim
            if req.worker is not None:
                break
            time.sleep(0.002)
        victim = req.worker
        _gate(victim is not None, "failover: request dispatched", failures)
        t_kill = time.monotonic()
        fd.kill_worker(victim)       # SIGKILL mid cold start

        res = req.result(timeout=deadline)
        t_recover = time.monotonic() - t_kill
        _gate(res["worker"] != victim,
              f"failover: sibling {res['worker']} served after {victim} "
              f"was SIGKILLed ({t_recover:.2f}s after kill)", failures)
        _gate(t_recover < deadline,
              f"failover: completed within the {deadline:.0f}s deadline",
              failures)
        diff = float(np.abs(np.asarray(res["output"]) - ref).max())
        _gate(diff == 0.0,
              f"failover: output bit-identical to isolated cold start "
              f"(max diff {diff:.1e})", failures)

        h = fd.health()
        for _ in range(600):         # restart fires under backoff
            if h["workers"][victim]["alive"]:
                break
            time.sleep(0.05)
            h = fd.health()
        wv = h["workers"][victim]
        _gate(wv["alive"] and h["stats"]["worker_restarts"] >= 1,
              f"failover: {victim} restarted (restarts={wv['restarts']})",
              failures)
        expect = fd.restart.delay(wv["restarts"])
        _gate(abs(wv["last_restart_delay"] - expect) < 1e-9,
              f"failover: restart waited the policy backoff "
              f"({wv['last_restart_delay']:.2f}s)", failures)

        res2 = fd.request("mnet", x, deadline_s=deadline).result(deadline)
        diff2 = float(np.abs(np.asarray(res2["output"]) - ref).max())
        _gate(diff2 == 0.0, "failover: fleet serves bit-identical after "
              "restart", failures)

        h = fd.health()
        leaked = (sum(w["in_flight"] for w in h["workers"].values())
                  + sum(h["queues"].values()) + h["batch_in_flight"])
        _gate(leaked == 0,
              f"failover: nothing leaked (in-flight+queued={leaked})",
              failures)
        print(f"  failovers={h['stats']['failovers']} "
              f"restarts={h['stats']['worker_restarts']} "
              f"recover_s={t_recover:.2f}")
    finally:
        fd.shutdown()


def run_priority(failures: list, *, image=16, width=0.25, n_batch=8):
    root = tempfile.mkdtemp(prefix="nnv12_frontdoor_prio_")
    fd = FrontDoor(root + "/fd", n_workers=2, max_inflight_per_worker=1,
                   interactive_reserve=1, worker_args=WORKER_ARGS)
    fd.start()
    try:
        fd.add_model("mnet", "repro.models.cnn:build_cnn",
                     name="mobilenet", image=image, width=width)
        _, x = build_cnn("mobilenet", image=image, width=width)
        fd.request("mnet", x).result(120)    # warm workers + seed the EWMA

        batch = [fd.request("mnet", x, lane=BATCH) for _ in range(n_batch)]
        time.sleep(0.05)                     # let the batch lane saturate
        t0 = time.monotonic()
        inter = fd.request("mnet", x, lane=INTERACTIVE)
        inter.result(120)
        delay = time.monotonic() - t0
        for b in batch:
            b.result(120)
        svc = fd._svc_ewma["mnet"]
        bound = max(0.5, 5 * svc)            # ~one service time + slack,
        #                                      NOT the n_batch*svc backlog
        _gate(delay < bound,
              f"priority: interactive delay {delay*1e3:.0f}ms bounded "
              f"(< {bound*1e3:.0f}ms) under {n_batch} queued batch "
              f"requests", failures)

        h0 = fd.health()["stats"]
        for tag, kw in (("rpc-floor", {"deadline_s": 1e-4}),
                        ("queue-est", {"deadline_s": max(0.05, 0.5 * svc),
                                       "lane": BATCH})):
            if tag == "queue-est":           # rebuild a saturating backlog
                flood = [fd.request("mnet", x, lane=BATCH)
                         for _ in range(4 * n_batch)]
            try:
                fd.request("mnet", x, **kw)
                shed = False
            except DeadlineExceeded:
                shed = True
            _gate(shed, f"priority: over-deadline request shed typed "
                  f"({tag})", failures)
            if tag == "queue-est":
                for b in flood:
                    b.result(120)
        h1 = fd.health()["stats"]
        _gate(h1["shed_deadline"] - h0["shed_deadline"] >= 2
              and (h1["dispatched_interactive"] + h1["dispatched_batch"]
                   - h0["dispatched_interactive"] - h0["dispatched_batch"])
              == 4 * n_batch,
              "priority: shed requests never consumed a dispatch slot",
              failures)
        print(f"  interactive_delay_ms={delay*1e3:.0f} "
              f"svc_ewma_ms={svc*1e3:.1f} "
              f"shed={h1['shed_deadline']}")
    finally:
        fd.shutdown()


def _poll_health(fd, wid, pred, *, timeout=10.0):
    """Wait for a worker heartbeat snapshot satisfying ``pred``; returns
    the snapshot (or the last one seen on timeout)."""
    deadline = time.monotonic() + timeout
    h = fd._workers[wid].health or {}
    while time.monotonic() < deadline:
        h = fd._workers[wid].health or {}
        if h and pred(h):
            break
        time.sleep(0.05)
    return h


def run_warm_transfer(failures: list, *, image=32, width=0.5,
                      sim_disk_bytes_per_s=4e6):
    root = tempfile.mkdtemp(prefix="nnv12_frontdoor_warm_")
    # 'super' store fmt gives measured local-read-bytes accounting; the
    # simulated disk bandwidth makes local read time REAL on CI hosts that
    # would otherwise serve the store from page cache at memory speed
    wargs = dict(WORKER_ARGS, store_fmt="super",
                 sim_disk_bytes_per_s=sim_disk_bytes_per_s)
    fd = FrontDoor(root + "/fd", n_workers=2, worker_args=wargs)
    fd.start()
    try:
        fd.add_model("mnet", "repro.models.cnn:build_cnn",
                     name="mobilenet", image=image, width=width)
        _, x = build_cnn("mobilenet", image=image, width=width)

        # w0's cold start IS the no-transfer baseline: no sibling holds the
        # model yet, so every byte comes off its (emulated) local disk
        h0 = _poll_health(fd, "w0", lambda h: "local_read_bytes" in h)
        pre0 = int(h0.get("local_read_bytes") or 0)
        r0 = fd.request("mnet", x, worker="w0").result(120)
        # wait for a post-completion heartbeat: "mnet" resident means the
        # job finished AND registered — only then is the byte count final
        # and only then does the front door see w0 as a transfer donor
        h0 = _poll_health(
            fd, "w0", lambda h: "mnet" in (h.get("resident") or ()))
        baseline = int(h0.get("local_read_bytes") or 0) - pre0
        _gate(r0["worker"] == "w0" and baseline > 0,
              f"warm-transfer: baseline cold start on w0 read "
              f"{baseline} bytes from local disk", failures)

        # w1 pinned: w0 is now a resident donor → the front door attaches
        # it as a peer and w1's ColdServer arms the fetch race
        h1 = _poll_health(fd, "w1", lambda h: "local_read_bytes" in h)
        pre1 = int(h1.get("local_read_bytes") or 0)
        r1 = fd.request("mnet", x, worker="w1").result(120)
        # the fetch outcome is folded into server stats by a job-done
        # callback — poll until a heartbeat carries it (and the engine
        # reports the race's cancelled reads fully drained)
        h1 = _poll_health(
            fd, "w1",
            lambda h: int((h.get("stats") or {})
                          .get("peer_layers_fetched") or 0) > 0
            and int((h.get("io_engine") or {}).get("in_flight", 1)) == 0)
        s1 = h1.get("stats") or {}
        local1 = int(h1.get("local_read_bytes") or 0) - pre1
        hd = _poll_health(
            fd, "w0",
            lambda h: int((h.get("stats") or {})
                          .get("transfers_served") or 0) > 0)
        donor = hd.get("stats") or {}

        _gate(r1["worker"] == "w1" and int(s1.get("peer_races") or 0) >= 1
              and int(donor.get("transfers_served") or 0) >= 1,
              f"warm-transfer: w1 raced a peer fetch and w0 served it "
              f"(layers={s1.get('peer_layers_fetched')} "
              f"bytes={s1.get('peer_bytes_fetched')})", failures)
        _gate(2 * local1 <= baseline,
              f"warm-transfer: w1 read >=2x fewer local disk bytes "
              f"({local1} vs baseline {baseline})", failures)
        diff = float(np.abs(np.asarray(r1["output"])
                            - np.asarray(r0["output"])).max())
        _gate(diff == 0.0,
              f"warm-transfer: fetched-state output bit-identical to "
              f"local cold start (max diff {diff:.1e})", failures)

        io1 = h1.get("io_engine") or {}
        fh = fd.health()
        stuck = (sum(w["in_flight"] for w in fh["workers"].values())
                 + sum(fh["queues"].values()) + fh["batch_in_flight"])
        _gate(int(io1.get("in_flight", -1)) == 0
              and int(io1.get("bytes_in_flight", -1)) == 0
              and int(s1.get("peer_crc_failures") or 0) == 0
              and stuck == 0,
              f"warm-transfer: nothing leaked after the race "
              f"(io_in_flight={io1.get('in_flight')} "
              f"bytes_in_flight={io1.get('bytes_in_flight')} "
              f"stuck={stuck})", failures)
        print(f"  baseline_bytes={baseline} w1_local_bytes={local1} "
              f"fetched_bytes={s1.get('peer_bytes_fetched')} "
              f"races={s1.get('peer_races')} "
              f"declined={s1.get('peer_races_declined')} "
              f"donor_transfers={donor.get('transfers_served')}")
        return r0, r1
    finally:
        fd.shutdown()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes + hard-fail gates (CI)")
    args = ap.parse_args(argv)
    from repro.core.compile_cache import setup_compile_cache

    setup_compile_cache()
    failures: list = []
    run_failover(failures, **({"image": 24, "width": 0.4}
                              if args.smoke else {}))
    run_priority(failures)
    run_warm_transfer(failures, **({"image": 24, "width": 0.4}
                                   if args.smoke else {}))
    if failures:
        print(f"\n{len(failures)} gate(s) failed:", file=sys.stderr)
        for f in failures:
            print(f"  - {f}", file=sys.stderr)
        if args.smoke:
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
