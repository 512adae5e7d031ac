#!/usr/bin/env python3
"""Bring-up smoke: serve smollm-360m cold on one TPU chip, end to end.

    python chip_smoke.py                # one chip: the cold-LLM main path
    python chip_smoke.py --four-chips   # four one-chip FrontDoor workers

The one-chip run drives the path a user calls — ``build_llm_graph`` ->
``ColdServer.add_model`` / ``decide()`` -> ``llm_bridge.cold_start_llm`` —
at smollm-360m's published widths (32 layers, d_model 960, 15/5 heads,
d_ff 2560, vocab 49152, tied embeddings) with random weights from a fixed
seed. It answers three requests of 8 new tokens: the first cold, the
others each after evicting the model, so cold again. It fails unless

  * the pipelined cold output equals the ``mode="sequential"`` output bit
    for bit;
  * the cold prefill logits, and the logits decode produced through the KV
    cache, match a float32 ``T.forward`` under
    ``default_matmul_precision("highest")`` within the bf16 tolerance below;
  * every request returns the same tokens and logits bit for bit;
  * the degradation ladder recorded nothing (no ``kernel_demoted``,
    ``decide_degraded``, ``plan_fallback``) and no circuit breaker opened.

``--four-chips`` runs only the fleet path, at the same unreduced widths and
depth: a one-worker FrontDoor gives the reference output, then a FrontDoor
with four workers, each bound to its own chip, answers one request per
worker; the workers must hold four distinct chips (the device nodes their
runtimes opened) and give outputs bit-identical to the reference. The parent process
touches JAX only after every worker has exited.

Printed readings are bring-up readings, not benchmark numbers. The last
line of a passing run is ``{"ok": true, "device": {...}}``; without a TPU
the script exits non-zero before doing any work.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

ARCH = "smollm-360m"
SEED = 0
NEW_TOKENS = 8
REQUESTS = 3
MODEL = "smollm"
# bf16 serving vs the float32 reference: largest |logit error| over the
# largest |reference logit|, separately for prefill and for decode
REL_TOL = 0.05
PLATFORM = "tpu"
# the fleet serves the same unreduced model, one copy per worker and chip
FLEET_BUILDER = "repro.core.llm_graph:named_llm_graph"
FLEET_KW = {"arch": ARCH, "seed": SEED}
FLEET_WORKERS = 4


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(("PASS " if ok else "FAIL ") + what, flush=True)
    if not ok:
        raise SmokeFailure(what)


def device_line(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu():
    import jax

    devs = jax.devices()
    if devs[0].platform != PLATFORM:
        raise SmokeFailure(
            f"no TPU found: JAX's default device is {devs[0].platform} "
            f"({devs[0].device_kind}); this smoke runs on the chip only")
    return devs


def rel_err(got, ref) -> float:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def reference_logits(cfg, params, tokens):
    """float32 ``T.forward`` over ``tokens`` at the highest matmul
    precision: (S, V) logits."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.models import transformer as T

    cfg32 = dataclasses.replace(cfg, dtype="float32")
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, t: T.forward(p, {"tokens": t}, cfg32)[0])
        out = fwd(p32, jnp.asarray(tokens, jnp.int32)[None])
    return np.asarray(out[0])


def serve_and_check(cfg, workdir: Path) -> None:
    """The one-chip main path at ``cfg``; raises ``SmokeFailure``."""
    import jax

    from repro.core.llm_graph import build_llm_graph
    from repro.executor.llm_bridge import cold_start_llm
    from repro.executor.server import ColdServer
    from repro.models import transformer as T

    t0 = time.perf_counter()
    params = T.init_params(jax.random.PRNGKey(SEED), cfg)
    graph, toks = build_llm_graph(cfg, params)
    prompt = toks[0]
    print(f"model {cfg.name}: {cfg.param_count() / 1e6:.1f}M params, "
          f"{len(graph)} engine layers, prompt {prompt.size} tokens "
          f"(built in {time.perf_counter() - t0:.2f}s)", flush=True)

    server = ColdServer(workdir / "server", n_little=3,
                        max_concurrent_preps=2)
    eng = server.add_model(MODEL, graph)
    stats = server.decide(MODEL, toks)
    check(not stats.get("degraded"), f"decide() planned without degrading "
          f"({stats['plan_generation_s']:.2f}s, "
          f"{stats['profile_calls']} profile calls)")
    kinds = Counter(kern + ("+cache" if cached else "")
                    for kern, cached in stats["choices"].values())
    print(f"plan: {dict(kinds)}", flush=True)

    results = []
    for i in range(REQUESTS):
        if i:
            server.evict(MODEL)
            check(MODEL not in server.resident_models(),
                  f"request {i}: model evicted, so this start is cold")
        res = cold_start_llm(eng, cfg, prompt, max_new_tokens=NEW_TOKENS,
                             n_little=3, server=server, model_name=MODEL,
                             keep_logits=True)
        stages = {k: round(v, 4)
                  for k, v in res.run.stage_seconds().items()}
        backend = [t for t in res.spans if t.kind == "compile"
                   and t.detail.startswith("backend_compile")]
        print(f"request {i}: first token {res.first_token_s:.4f}s, decode "
              f"ready {res.decode_ready_s:.4f}s, stage seconds {stages}, "
              f"backend compiles or cache loads {len(backend)} "
              f"({sum(t.end - t.start for t in backend):.2f}s), tokens "
              f"{res.tokens}", flush=True)
        check(MODEL in server.resident_models(),
              f"request {i}: staged weights resident after the cold start")
        results.append(res)

    first = results[0]
    check(len(first.tokens) == NEW_TOKENS
          and first.logits.shape == (NEW_TOKENS, cfg.vocab_size),
          f"{NEW_TOKENS} tokens with their logits rows")
    for i, res in enumerate(results[1:], 1):
        check(res.tokens == first.tokens
              and np.array_equal(np.asarray(res.run.output),
                                 np.asarray(first.run.output))
              and np.array_equal(res.logits, first.logits),
              f"request {i}: cold again after eviction, bit-identical to "
              f"request 0")

    seq = eng.run_cold(toks, mode="sequential")
    cold_out = np.asarray(first.run.output)
    check(np.array_equal(cold_out, np.asarray(seq.output)),
          "pipelined cold prefill equals the sequential run bit for bit")

    # one causal reference pass covers the prompt and every decoded token
    ext = np.concatenate([prompt, first.tokens[:-1]])
    ref = reference_logits(cfg, params, ext)
    S = prompt.size
    e_pre = rel_err(cold_out[0], ref[:S])
    e_dec = rel_err(first.logits[1:], ref[S:])
    agree = float((cold_out[0].argmax(-1) == ref[:S].argmax(-1)).mean())
    print(f"vs float32 reference: prefill rel err {e_pre:.6f}, decode rel "
          f"err {e_dec:.6f} (tolerance {REL_TOL}); prefill argmax "
          f"agreement {agree:.4f}", flush=True)
    check(e_pre <= REL_TOL, "cold prefill logits match the f32 reference")
    check(e_dec <= REL_TOL,
          "decode-through-cache logits match the f32 reference")

    repairs = eng.repairs.counts()
    journal = eng.store.root / "repairs.jsonl"
    check(not repairs and not (journal.exists() and journal.stat().st_size),
          f"degradation ladder recorded nothing ({repairs})")
    check(not eng.breaker.open_keys(), "no circuit breaker opened")

    from repro.core import staging
    from repro.ioengine import get_io_engine

    cc = eng.compile_cache.stats
    print(f"I/O backend {get_io_engine().name}; staging route "
          f"{staging.route_name()}", flush=True)
    print(f"executable cache: {cc['misses']} compiled "
          f"({cc['compile_s']:.2f}s), {cc['disk_hits']} from disk, "
          f"{cc['hits']} in memory, {cc['deserialize_failures']} unreadable",
          flush=True)


def four_chips(workdir: Path) -> dict:
    """Four one-chip workers vs one worker's isolated cold start. The
    parent stays off the chips until every worker has exited."""
    from repro.core.llm_graph import example_tokens
    from repro.configs import get_config
    from repro.executor.frontdoor import FrontDoor
    from repro.faults import HeartbeatPolicy

    x = example_tokens(get_config(FLEET_KW["arch"]).vocab_size)
    # building and planning the full-width model keeps a worker busy for
    # tens of seconds; this phase tests placement, not failover
    fd_kw = dict(spawn_timeout_s=900.0, worker_args={"n_little": 2},
                 heartbeat=HeartbeatPolicy(interval_s=0.5,
                                           miss_threshold=120))

    t0 = time.perf_counter()
    with FrontDoor(workdir / "fd", n_workers=1, **fd_kw) as fd:
        ref_dev = fd.health()["workers"]["w0"]["device"]
        check(ref_dev.get("platform") == PLATFORM,
              f"the reference worker found a TPU ({ref_dev})")
        fd.add_model(MODEL, FLEET_BUILDER, **FLEET_KW)
        ref = fd.request(MODEL, x).result(900)
    ref_out = np.asarray(ref["output"])
    print(f"reference: one worker on {ref_dev}, output {ref_out.shape} "
          f"({time.perf_counter() - t0:.1f}s)", flush=True)

    t0 = time.perf_counter()
    wids = [f"w{i}" for i in range(FLEET_WORKERS)]
    with FrontDoor(workdir / "fd", n_workers=FLEET_WORKERS, **fd_kw) as fd:
        fd.add_model(MODEL, FLEET_BUILDER, **FLEET_KW)
        reqs = [fd.request(MODEL, x, worker=wid) for wid in wids]
        outs = [r.result(900) for r in reqs]
        devs = {wid: w["device"] for wid, w in fd.health()["workers"].items()}
    print(f"fleet: {len(outs)} requests in {time.perf_counter() - t0:.1f}s",
          flush=True)
    for wid, d in devs.items():
        print(f"  {wid}: {d}", flush=True)
    check(all(d.get("platform") == PLATFORM and d.get("count") == 1
              for d in devs.values()),
          "every worker sees exactly one TPU chip")
    check([o["worker"] for o in outs] == wids,
          "each worker answered its own request")
    check(all(np.array_equal(np.asarray(o["output"]), ref_out)
              for o in outs),
          "every worker's output is bit-identical to the isolated one")

    # JAX numbers each one-chip process's chip 0 at coords (0, 0, 0); the
    # device nodes each worker's runtime holds open tell the chips apart
    nodes = [set(d.get("nodes") or ()) for d in devs.values()]
    check(all(nodes) and len(set().union(*nodes)) == sum(map(len, nodes)),
          f"the workers hold {FLEET_WORKERS} distinct chips (device nodes "
          f"{[sorted(n) for n in nodes]})")

    import jax

    devs = jax.devices()
    check(devs[0].platform == PLATFORM and len(devs) == FLEET_WORKERS,
          f"after the fleet exits, this host shows {FLEET_WORKERS} TPU chips")
    return device_line(devs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four one-chip worker fleet path")
    args = ap.parse_args(argv)

    from repro.core.compile_cache import setup_compile_cache

    print(f"compile caches under {setup_compile_cache()}", flush=True)
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            if args.four_chips:
                device = four_chips(Path(tmp))
            else:
                devs = require_tpu()
                print(f"device: {devs[0]} ({devs[0].device_kind}), "
                      f"{len(devs)} visible", flush=True)
                from repro.configs import get_config

                serve_and_check(get_config(ARCH), Path(tmp))
                device = device_line(devs)
    except SmokeFailure as e:
        print(f"chip smoke failed: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
