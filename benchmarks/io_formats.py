"""Cold weight-read formats: packed per-layer bundle vs model-level
super-bundle.

Measures the per-layer 'weights reading' op the scheduler pipelines, across
the on-disk layouts the ``LayerStore`` supports:

  bundle       packed single-blob layer file, ONE open + one sequential
               read — the reference arm every speedup is relative to
  bundle_mmap  same file, zero-copy ``np.memmap`` views — the read op is
               metadata-only; payload pages fault in later, inside
               transform/stage, off the critical exec chain
  super        v2 model-level super-bundle: the WHOLE model in one file,
               read through one shared mmap — ONE open per model;
               ``super`` materializes each layer's bytes (real I/O in the
               read op), ``super_mmap`` returns zero-copy views
  *_touch      additionally faults every payload byte in, so a lazy row
               can't hide I/O that merely moved downstream

The super-bundle store is built with ``superbundle.migrate`` from the
per-layer bundle tree, so the migration path is exercised on every run.
Every run cross-checks tensor equivalence across all formats and read
modes against the bundle's materializing read, and counts the file opens
a full-model sweep performs (bundle: N_layers, super: 1) — both are hard
failures on mismatch, which is what CI runs ``--smoke`` for.

The durability arms (``bench_durability``) exercise the v3 container's
crash-atomicity layer and are also hard gates in ``--smoke``:

  verify overhead   full cold sweeps with ``verify="never"`` vs the
                    default ``verify="lazy"`` — the lazy CRC-32C audit
                    must cost <= 5% on the engine's cold read path (one
                    open + recovery + zero-copy mmap reads); the eager
                    full-file audit (fsck mode) is timed and reported
  crash injection   an in-place cache commit is crashed at every phase
                    (after journal fsync / mid-slot / pre-header / torn
                    header / pre-commit-record); reopening must leave the
                    entry fully applied or fully rolled back — raw
                    weights byte-identical, no torn bytes ever served
  compaction        dropped entries leave dead extents; ``compact`` must
                    reclaim them to exactly zero slack beyond alignment

Workloads: cnn_zoo models (2 tensors/layer) and an LLM decoder graph (10+
tensors per tblock).

Run: PYTHONPATH=src python benchmarks/io_formats.py [--smoke]
"""
from __future__ import annotations

import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

from repro.checkpoint import LayerStore
from repro.checkpoint.superbundle import migrate
from repro.core.oscache import CAN_DROP, drop_page_cache

try:
    from benchmarks.common import csv_line
except ModuleNotFoundError:  # invoked as `python benchmarks/io_formats.py`
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from benchmarks.common import csv_line


def _cnn_weights(model: str, image: int, width: float) -> Dict[str, dict]:
    from repro.models.cnn import build_cnn

    layers, _ = build_cnn(model, image=image, width=width)
    return {l.spec.name: l.weights for l in layers if l.weights}


def _llm_weights(num_layers: int, d_model: int) -> Dict[str, dict]:
    import jax

    from repro.configs import get_config
    from repro.core.llm_graph import build_llm_graph
    from repro.models import transformer as T

    cfg = get_config("smollm-360m").reduced(
        num_layers=num_layers, d_model=d_model, d_ff=d_model * 3,
        num_heads=8, num_kv_heads=4, head_dim=d_model // 8,
        vocab_size=2048)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    graph, _ = build_llm_graph(cfg, params)
    return {l.spec.name: l.weights for l in graph if l.weights}


def _sweep(read_fn, names: List[str], repeats: int, reset=None) -> float:
    """Best-of-N full-model sweep: seconds to read every layer once,
    page cache dropped first when the host allows (paper methodology).
    ``reset`` runs before each pass (e.g. close the super-bundle's shared
    mmap so every pass pays the cold open)."""
    best = float("inf")
    for _ in range(repeats):
        if reset is not None:
            reset()
        if CAN_DROP:
            drop_page_cache()
        t0 = time.perf_counter()
        for n in names:
            read_fn(n)
        best = min(best, time.perf_counter() - t0)
    return best


def _touch(w: Dict[str, np.ndarray]) -> int:
    total = 0
    for v in w.values():
        total += int(v.view(np.uint8).reshape(-1)[:: 4096].sum())
    return total


def _count_opens(store: LayerStore, names: List[str]) -> int:
    """File opens one cold full-model read sweep performs."""
    store.close()
    store.open_count = 0
    for n in names:
        store.read_raw(n)
    return store.open_count


def _check_equivalence(stores: Dict[str, LayerStore], names: List[str]):
    """Every format and read mode must return the bundle's materialized
    tensors for every layer — a mismatch is a hard failure (CI gates on
    it)."""
    ref = stores["bundle"]
    for n in names:
        want = ref.read_raw(n, mmap=False)
        for label, st in stores.items():
            for mmap in (False, True):
                if st is ref and not mmap:
                    continue
                got = st.read_raw(n, mmap=mmap)
                arm = f"{label}{'_mmap' if mmap else ''}/{n}"
                if set(got) != set(want):
                    raise AssertionError(
                        f"equivalence mismatch: {arm} keys {set(got)} "
                        f"!= bundle keys {set(want)}")
                for k in want:
                    if got[k].dtype != want[k].dtype or not np.array_equal(
                            np.asarray(got[k]), np.asarray(want[k])):
                        raise AssertionError(
                            f"equivalence mismatch: {arm}/{k}")


def bench_model(name: str, weights: Dict[str, dict], repeats: int = 3,
                print_csv: bool = True) -> Dict[str, float]:
    names = list(weights)
    with tempfile.TemporaryDirectory(prefix=f"iofmt_{name}_") as td:
        s_bun = LayerStore(Path(td) / "bundle", fmt="bundle")
        for ln, w in weights.items():
            s_bun.write_raw(ln, w)
        # super store: migrated from the per-layer bundle tree, laid out in
        # graph order — exercises the migration path every run. verify=never:
        # these arms time FORMAT byte movement; checksum-audit cost has its
        # own dedicated arm (and gate) in bench_durability, and the reopen
        # per pass would otherwise re-audit every payload byte every sweep
        s_sup = LayerStore(Path(td) / "super", fmt="super", verify="never")
        migrate(Path(td) / "bundle", Path(td) / "super" / "model.superbundle",
                order=names)

        _check_equivalence({"bundle": s_bun, "super": s_sup}, names)
        opens = {
            "bundle": _count_opens(s_bun, names),
            "super": _count_opens(s_sup, names),
        }
        assert opens["super"] == 1, (
            f"super-bundle must be ONE open per model, saw {opens['super']}")
        assert opens["bundle"] == len(names), opens

        t_bun = _sweep(lambda n: s_bun.read_raw(n, mmap=False), names, repeats)
        t_map = _sweep(lambda n: s_bun.read_raw(n, mmap=True), names, repeats)
        t_map_touch = _sweep(
            lambda n: _touch(s_bun.read_raw(n, mmap=True)), names, repeats)
        t_sup = _sweep(lambda n: s_sup.read_raw(n, mmap=False), names,
                       repeats, reset=s_sup.close)
        t_sup_map = _sweep(lambda n: s_sup.read_raw(n, mmap=True), names,
                           repeats, reset=s_sup.close)
        t_sup_touch = _sweep(
            lambda n: _touch(s_sup.read_raw(n, mmap=True)), names,
            repeats, reset=s_sup.close)

    per_layer = 1.0 / max(len(names), 1)
    res = {
        "bundle_s": t_bun, "bundle_mmap_s": t_map,
        "bundle_mmap_touch_s": t_map_touch,
        "super_s": t_sup, "super_mmap_s": t_sup_map,
        "super_mmap_touch_s": t_sup_touch,
        "opens_bundle": opens["bundle"], "opens_super": opens["super"],
        "speedup_mmap": t_bun / max(t_map, 1e-9),
        "speedup_mmap_touch": t_bun / max(t_map_touch, 1e-9),
        "speedup_super": t_bun / max(t_sup, 1e-9),
        "speedup_super_mmap": t_bun / max(t_sup_map, 1e-9),
    }
    if print_csv:
        print(csv_line(f"io_formats/{name}/bundle", t_bun * per_layer,
                       f"layers={len(names)};opens={opens['bundle']}"))
        print(csv_line(f"io_formats/{name}/bundle_mmap", t_map * per_layer,
                       f"speedup={res['speedup_mmap']:.2f}x"))
        print(csv_line(f"io_formats/{name}/bundle_mmap_touch",
                       t_map_touch * per_layer,
                       f"speedup={res['speedup_mmap_touch']:.2f}x"))
        print(csv_line(f"io_formats/{name}/super", t_sup * per_layer,
                       f"speedup={res['speedup_super']:.2f}x;opens=1"))
        print(csv_line(f"io_formats/{name}/super_mmap", t_sup_map * per_layer,
                       f"speedup={res['speedup_super_mmap']:.2f}x;opens=1"))
        print(csv_line(f"io_formats/{name}/super_mmap_touch",
                       t_sup_touch * per_layer,
                       f"speedup={t_bun / max(t_sup_touch, 1e-9):.2f}x"))
        ok = t_sup_map <= t_map
        print(f"# {name}: super_mmap <= bundle_mmap: {ok} "
              f"({t_sup_map * per_layer * 1e6:.1f} vs "
              f"{t_map * per_layer * 1e6:.1f} us/layer), "
              f"opens {opens['super']} vs {opens['bundle']}")
    return res


def bench_durability(repeats: int = 5, print_csv: bool = True,
                     smoke: bool = False) -> Dict[str, float]:
    """Format-v3 durability arms: checksum-verify overhead on the cold read
    path, eager-audit cost, crash-injection recovery at every commit phase,
    and dead-extent compaction. All assertions are hard failures."""
    import shutil
    import struct

    import repro.checkpoint.superbundle as sbmod
    from repro.checkpoint.bundle import ALIGN
    from repro.checkpoint.superbundle import (
        InjectedCrash, SuperBundle, compact, drop_cache_entry, journal_path,
        set_cache_entry, write_superbundle,
    )

    weights = _llm_weights(num_layers=3 if smoke else 6,
                           d_model=256 if smoke else 512)
    names = list(weights)
    cached = names[::2]
    res: Dict[str, float] = {}
    with tempfile.TemporaryDirectory(prefix="iofmt_durability_") as td:
        p = Path(td) / "model.superbundle"
        cache = {n: {"k": weights[n]} for n in cached}
        write_superbundle(p, weights, cache=cache, order=names)

        # -- checksum-verify overhead on COLD reads (the engine's default
        #    path: one open + journal recovery + zero-copy mmap views; lazy
        #    keeps CRC audits off it by design) -----------------------------
        def sweep(verify: str) -> float:
            best = float("inf")
            for _ in range(repeats):
                if CAN_DROP:
                    drop_page_cache()
                t0 = time.perf_counter()
                with SuperBundle(p, verify=verify) as sb:
                    for n in names:
                        sb.read_raw(n)
                best = min(best, time.perf_counter() - t0)
            return best

        t_never, t_lazy = sweep("never"), sweep("lazy")
        overhead = t_lazy / max(t_never, 1e-9) - 1.0
        if CAN_DROP:
            drop_page_cache()
        t0 = time.perf_counter()
        SuperBundle(p, verify="eager").close()  # full-file audit (fsck)
        t_eager = time.perf_counter() - t0
        res.update(verify_never_s=t_never, verify_lazy_s=t_lazy,
                   verify_overhead=overhead, eager_audit_s=t_eager)
        if smoke:
            assert t_lazy <= t_never * 1.05 + 2e-3, (
                f"lazy checksum mode costs {overhead:+.1%} on the cold mmap "
                f"read path ({t_lazy:.4f}s vs {t_never:.4f}s; gate: <=5%)")

        # -- crash injection: every commit phase must resolve to fully
        #    applied or fully rolled back on reopen -------------------------
        layer = cached[0]
        old = {k: np.array(np.asarray(v)) for k, v in cache[layer]["k"].items()}
        new = {k: np.full_like(np.asarray(v), 0.5) for k, v in old.items()}
        phases = [("journal-synced", False, "old"),
                  ("slot", True, "dropped"),
                  ("header", False, "new"),
                  ("header", True, "new"),
                  ("header-written", False, "new")]
        for i, (phase, partial, expect) in enumerate(phases):
            q = Path(td) / f"crash{i}.superbundle"
            shutil.copy(p, q)

            def hook(ph, **ctx):
                if ph != phase:
                    return
                if partial and ph == "slot":
                    f, off = ctx["file"], ctx["offset"]
                    payload = ctx["payload"]
                    f.seek(off)
                    f.write(payload[: len(payload) // 2])  # torn slot write
                    f.flush()
                if partial and ph == "header":
                    f, hdr = ctx["file"], ctx["header"]
                    f.seek(0)
                    f.write(b"NNVS" + struct.pack("<I", 3) + hdr[:40])
                    f.flush()  # torn header write
                raise InjectedCrash(ph)

            sbmod._crash_hook = hook
            try:
                set_cache_entry(q, layer, "k", new)
                raise AssertionError(f"crash hook never fired at {phase}")
            except InjectedCrash:
                pass
            finally:
                sbmod._crash_hook = None
            t0 = time.perf_counter()
            with SuperBundle(q, verify="eager") as sb:
                t_rec = time.perf_counter() - t0
                for n in names:  # raw weights byte-identical in every arm
                    got = sb.read_raw(n, materialize=True)
                    for k, v in weights[n].items():
                        assert np.array_equal(np.asarray(got[k]),
                                              np.asarray(v)), (phase, n, k)
                if expect == "dropped":
                    assert not sb.has_cached(layer, "k"), phase
                else:
                    assert not sb.dropped, (phase, sb.dropped)
                    want = old if expect == "old" else new
                    got = sb.read_cached(layer, "k", materialize=True)
                    for k, v in want.items():
                        assert np.array_equal(np.asarray(got[k]),
                                              np.asarray(v)), (phase, k)
            assert journal_path(q).stat().st_size == 0, phase
            tag = f"{phase}{'_torn' if partial else ''}"
            res[f"recover_{tag}_s"] = t_rec
            if print_csv:
                print(csv_line(f"io_formats/durability/recover_{tag}",
                               t_rec, f"outcome={expect}"))

        # -- compaction: drops leave dead extents; compact reclaims them to
        #    zero slack (< one alignment unit per layer, trivially) ---------
        for n in cached:
            assert drop_cache_entry(p, n, "k")
        with SuperBundle(p) as sb:
            dead = sb.reclaimable_bytes()
            size_before = sb.file_size()
        assert dead > 0, "drops must leave reclaimable dead extents"
        t0 = time.perf_counter()
        stats = compact(p)
        t_compact = time.perf_counter() - t0
        with SuperBundle(p, verify="eager") as sb:
            # stricter than the acceptance bound (< ALIGN per layer):
            # compaction must leave NO dead bytes at all
            slack = sb.reclaimable_bytes()
            assert slack == 0, (slack, ALIGN * len(names))
            assert sb.cache_disk_bytes() == 0
        assert stats["reclaimed_bytes"] == size_before - stats["file_size"]
        res.update(compact_s=t_compact,
                   reclaimed_bytes=float(stats["reclaimed_bytes"]))
        if print_csv:
            print(csv_line("io_formats/durability/verify_lazy_sweep", t_lazy,
                           f"overhead={overhead:+.1%}_vs_never"))
            print(csv_line("io_formats/durability/eager_audit", t_eager,
                           "full-file_fsck"))
            print(csv_line("io_formats/durability/compact", t_compact,
                           f"reclaimed={stats['reclaimed_bytes']}B;slack=0"))
    return res


def bench_async(name: str, weights: Dict[str, dict], repeats: int = 3,
                print_csv: bool = True, smoke: bool = False,
                depth: int = 8) -> Dict[str, float]:
    """Async read-engine arms vs the sync reference path, per backend.

    For every backend that passes its self-check on this host (uring where
    the kernel offers it, the portable aio thread pool, the forced-sync
    degenerate backend), a cold full-model sweep is timed through the
    store's ``submit_read_raw`` extent API at queue depth 1 (submit, reap,
    next — the async path's floor) and at ``depth`` (a sliding window of
    in-flight reads, the executor's steady state). The sync ``read_raw``
    path stays as the reference arm.

    Hard gate (always): every backend × depth reaps tensors bit-identical
    to the sync reference. ``--smoke`` adds timing gates: depth 1 must not
    fall meaningfully behind sync (submit/reap bookkeeping bound), and
    depth > 1 must at least match the sync arm's cold throughput."""
    from repro.ioengine import IOEngine, available_backends

    names = list(weights)
    res: Dict[str, float] = {}
    with tempfile.TemporaryDirectory(prefix=f"iofmt_async_{name}_") as td:
        store = LayerStore(Path(td) / "super", fmt="super", verify="never")
        for ln, w in weights.items():
            store.write_raw(ln, w)
        store._super(flush_all=True)
        ref = {n: {k: np.array(np.asarray(v), copy=True)
                   for k, v in store.read_raw(n, mmap=False).items()}
               for n in names}

        t_sync = _sweep(lambda n: store.read_raw(n, mmap=False), names,
                        repeats, reset=store.close)
        res["sync_s"] = t_sync
        per_layer = 1.0 / max(len(names), 1)
        if print_csv:
            print(csv_line(f"io_async/{name}/sync", t_sync * per_layer,
                           f"layers={len(names)};reference"))

        def sweep_depth(engine: IOEngine, window: int) -> float:
            def reset():
                store.close()
                # reopen outside the timed region is NOT done: the cold
                # open is part of the read path, same as the sync arm
            best = float("inf")
            for _ in range(repeats):
                reset()
                if CAN_DROP:
                    drop_page_cache()
                t0 = time.perf_counter()
                pending: List = []

                def reap_one():
                    ln, h = pending.pop(0)
                    got = h.wait()
                    for k, v in ref[ln].items():
                        if not np.array_equal(np.asarray(got[k]), v):
                            raise AssertionError(
                                f"async/{engine.name}/d{window}: "
                                f"{ln}/{k} differs from sync arm")
                    h.release()

                for n in names:
                    while len(pending) >= window:
                        reap_one()   # window full: oldest read reaps first
                    pending.append((n, store.submit_read_raw(engine, n)))
                while pending:
                    reap_one()
                best = min(best, time.perf_counter() - t0)
            return best

        for backend in available_backends():
            engine = IOEngine(backend=backend)
            try:
                t1 = sweep_depth(engine, 1)
                td_ = sweep_depth(engine, depth)
            finally:
                engine.close()
            res[f"{backend}_d1_s"] = t1
            res[f"{backend}_d{depth}_s"] = td_
            if print_csv:
                print(csv_line(f"io_async/{name}/{backend}_d1",
                               t1 * per_layer,
                               f"vs_sync={t_sync / max(t1, 1e-9):.2f}x"))
                print(csv_line(f"io_async/{name}/{backend}_d{depth}",
                               td_ * per_layer,
                               f"vs_sync={t_sync / max(td_, 1e-9):.2f}x"))
            if smoke:
                assert t1 <= t_sync * 1.25 + 5e-3, (
                    f"{backend} depth-1 async sweep {t1:.4f}s falls behind "
                    f"sync reference {t_sync:.4f}s (gate: <=25% + 5ms)")
                assert td_ <= t_sync * 1.05 + 5e-3, (
                    f"{backend} depth-{depth} sweep {td_:.4f}s slower than "
                    f"sync reference {t_sync:.4f}s — depth must at least "
                    f"match the sync arm's cold throughput")
        store.close()
    return res


def bench_quantized(print_csv: bool = True, smoke: bool = False,
                    num_layers: int = 8) -> Dict[str, float]:
    """Quantized transform-cache arms (format v4): three ColdEngines over
    the SAME LLM graph, differing only in eligible kernels —

      bf16   bf16_cast cache entries (the lossless reference arm)
      int8   per-channel int8 extents (+bf16_cast for the embed gather)
      int4   nibble-packed int4 extents (+bf16_cast for the embed)

    Each arm runs Algorithm-1 ``decide()`` under the deterministic
    synthetic cost model (quantized entries = smaller read, nonzero
    dequant surcharge), then a REAL ``run_cold`` whose cold cache bytes
    are metered via the store's ``bytes_served()`` counter.

    ``--smoke`` hard gates (the PR's acceptance criteria):
      * decide() picks the quantized (kernel, cache) choice for a majority
        of matmul-dominated layers (tblocks + lm_head);
      * measured cold bytes served: int8 >= 1.8x and int4 >= 3x below the
        bf16 cache arm;
      * outputs stay within per-dtype tolerance of the bf16 arm
        (correlation > 0.99 for int8, > 0.8 for int4)."""
    from repro.core.engine import ColdEngine
    from repro.core.llm_graph import tiny_llm_graph
    from repro.core.profiler import SyntheticProfiler

    graph, x = tiny_llm_graph(num_layers)
    matmul_layers = [l.spec.name for l in graph
                     if l.spec.op_type in ("tblock", "lmhead")]
    arms = [("bf16", ["bf16_cast"]),
            ("int8", ["int8", "bf16_cast"]),
            ("int4", ["int4", "bf16_cast"])]
    res: Dict[str, float] = {}
    outputs: Dict[str, np.ndarray] = {}
    with tempfile.TemporaryDirectory(prefix="iofmt_quant_") as td:
        for arm, allow in arms:
            eng = ColdEngine(graph, Path(td) / arm, store_fmt="super",
                             allow_lossy=True, kernel_allowlist=allow)
            eng.profiler_factory = SyntheticProfiler
            # no wall-clock interference calibration: the pick gates must
            # be a pure function of the synthetic cost model, not of how
            # much I/O the preceding benchmark sections churned
            stats = eng.decide(x, n_little=2, calibrate_interference=False)
            picked = {l.spec.name: c for l, c in zip(eng.layers,
                                                     eng.plan.choices)}
            n_quant = sum(1 for n in matmul_layers
                          if picked[n].kernel == arm and picked[n].use_cache)
            served0 = eng.store.bytes_served()
            t0 = time.perf_counter()
            out = eng.run_cold(x, n_little=2)
            t_cold = time.perf_counter() - t0
            cold_bytes = eng.store.bytes_served() - served0
            outputs[arm] = np.asarray(out.output, np.float32)
            res[f"{arm}_cold_bytes"] = float(cold_bytes)
            res[f"{arm}_cold_s"] = t_cold
            res[f"{arm}_planned_cached_bytes"] = float(
                stats["planned_cold_read_bytes"]["cached_bytes"])
            res[f"{arm}_quant_picks"] = float(n_quant)
            if print_csv:
                print(csv_line(
                    f"io_quant/{arm}/cold", t_cold,
                    f"bytes={cold_bytes};quant_picks={n_quant}"
                    f"/{len(matmul_layers)}"))
            if smoke and arm in ("int8", "int4"):
                assert n_quant > len(matmul_layers) // 2, (
                    f"{arm}: decide() picked quantized cache for only "
                    f"{n_quant}/{len(matmul_layers)} matmul layers")
        for arm, floor in (("int8", 1.8), ("int4", 3.0)):
            ratio = res["bf16_cold_bytes"] / max(res[f"{arm}_cold_bytes"], 1)
            res[f"{arm}_bytes_ratio"] = ratio
            a = outputs[arm].ravel()
            b = outputs["bf16"].ravel()
            corr = float(np.corrcoef(a, b)[0, 1])
            res[f"{arm}_corr"] = corr
            if print_csv:
                print(f"# quantized/{arm}: cold-bytes {ratio:.2f}x below "
                      f"bf16 (floor {floor}x), output corr {corr:.4f}")
            if smoke:
                assert ratio >= floor, (
                    f"{arm} arm read {res[f'{arm}_cold_bytes']:.0f}B cold vs "
                    f"bf16 {res['bf16_cold_bytes']:.0f}B — "
                    f"{ratio:.2f}x < required {floor}x")
                tol = 0.99 if arm == "int8" else 0.8
                assert corr > tol, (
                    f"{arm} output corr {corr:.4f} <= {tol} vs bf16 arm")
    return res


def run(print_csv: bool = True, smoke: bool = False) -> Dict[str, Dict[str, float]]:
    if smoke:
        cases: List[Tuple[str, Dict[str, dict]]] = [
            ("mobilenet", _cnn_weights("mobilenet", image=24, width=0.5)),
            ("llm_tiny", _llm_weights(num_layers=3, d_model=256)),
        ]
        repeats = 3
    else:
        cases = [
            ("mobilenet", _cnn_weights("mobilenet", image=40, width=1.0)),
            ("resnet18", _cnn_weights("resnet18", image=40, width=1.0)),
            ("squeezenet", _cnn_weights("squeezenet", image=40, width=1.0)),
            ("llm_smollm", _llm_weights(num_layers=8, d_model=512)),
        ]
        repeats = 3
    out = {}
    for name, weights in cases:
        out[name] = bench_model(name, weights, repeats=repeats,
                                print_csv=print_csv)
    # async engine arms on the LLM workload (many tensors/extents per layer
    # — where queue depth pays); the CNN case covers the small-extent shape
    out["async_llm"] = bench_async(
        cases[-1][0], cases[-1][1], repeats=repeats, print_csv=print_csv,
        smoke=smoke)
    out["durability"] = bench_durability(print_csv=print_csv, smoke=smoke)
    out["quantized"] = bench_quantized(print_csv=print_csv, smoke=smoke)
    if print_csv and not CAN_DROP:
        print("# warning: cannot drop page cache — warm-cache numbers",
              file=sys.stderr)
    return out


if __name__ == "__main__":
    smoke = "--smoke" in sys.argv
    print("name,us_per_call,derived")
    run(print_csv=True, smoke=smoke)
