"""Async I/O engine (PR 7): backend probe/self-check, pinned buffer pool,
byte-budget admission, depth planning, extent-granular store reads, fault
injection at the new engine sites, and the async/sync bit-identity the
whole refactor is gated on.
"""
import os
import threading
import time

import numpy as np
import pytest

import repro.ioengine as iomod
from repro.checkpoint import LayerStore
from repro.faults import FaultInjector, ReadFault, RetryPolicy
from repro.ioengine import (
    IOEngine, POOL_BYTES, PinnedBufferPool, StageEngine, available_backends,
    get_io_engine, reset_io_engine, reset_stage_engine,
)


@pytest.fixture(autouse=True)
def _fresh_singletons():
    reset_io_engine()
    reset_stage_engine()
    yield
    reset_io_engine()
    reset_stage_engine()


def _write_file(path, nbytes, seed=7):
    data = (np.arange(nbytes, dtype=np.int64) * seed % 251).astype(np.uint8)
    path.write_bytes(data.tobytes())
    return data


# ---------------------------------------------------------------------------
# backend probe / self-check / override
# ---------------------------------------------------------------------------
def test_probe_always_lands_on_a_backend():
    eng = IOEngine()
    try:
        assert eng.name in ("uring", "aio", "sync")
    finally:
        eng.close()


def test_available_backends_include_portable_floor():
    avail = available_backends()
    # aio (thread pool over preadv) and sync are pure-python portable
    assert "aio" in avail and "sync" in avail


def test_env_override_forces_backend(monkeypatch):
    monkeypatch.setenv("REPRO_IO_ENGINE", "sync")
    eng = IOEngine()
    try:
        assert eng.name == "sync"
    finally:
        eng.close()


def test_unknown_backend_rejected():
    with pytest.raises(ValueError):
        IOEngine(backend="nvme-of")


def test_singleton_reset(tmp_path):
    a = get_io_engine()
    assert get_io_engine() is a
    reset_io_engine()
    b = get_io_engine()
    assert b is not a


# ---------------------------------------------------------------------------
# reads: correctness + cross-backend bit-identity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", available_backends())
def test_reads_bit_identical_to_file(tmp_path, backend):
    data = _write_file(tmp_path / "blob", 300_000)
    eng = IOEngine(backend=backend)
    fd = os.open(tmp_path / "blob", os.O_RDONLY)
    try:
        cases = [(0, 4096), (4096, 65536), (100_003, 31_337), (0, 300_000)]
        tickets = [eng.submit(fd, off, n, key=f"c{i}")
                   for i, (off, n) in enumerate(cases)]
        for (off, n), t in zip(cases, tickets):
            view = t.wait(5.0)
            assert not view.flags.writeable  # staging contract
            assert np.array_equal(view, data[off:off + n])
            t.release()
        snap = eng.snapshot()
        assert snap["in_flight"] == 0 and snap["bytes_in_flight"] == 0
        assert snap["reaped"] == len(cases)
    finally:
        os.close(fd)
        eng.close()


def test_short_file_read_is_an_error(tmp_path):
    _write_file(tmp_path / "blob", 1000)
    eng = IOEngine(backend="aio")
    fd = os.open(tmp_path / "blob", os.O_RDONLY)
    try:
        t = eng.submit(fd, 512, 4096, key="short")
        with pytest.raises(Exception):
            t.wait(5.0)
    finally:
        os.close(fd)
        eng.close()


# ---------------------------------------------------------------------------
# pinned buffer pool
# ---------------------------------------------------------------------------
def test_pool_recycles_size_classes():
    pool = PinnedBufferPool(max_bytes=1 << 20, pin=False)
    a = pool.acquire(5000)
    cap = a.capacity
    pool._release(a)
    b = pool.acquire(6000)   # same power-of-2 class -> recycled slab
    assert b.capacity == cap and pool.stats["reuses"] == 1
    pool._release(b)
    pool.close()


def test_pool_release_is_idempotent():
    pool = PinnedBufferPool(max_bytes=1 << 20, pin=False)
    a = pool.acquire(4096)
    a.release()
    a.release()  # double release must not double-free the slab
    x = pool.acquire(4096)
    y = pool.acquire(4096)
    assert x.arr is not y.arr
    pool.close()


def test_pool_overflow_allocs_beyond_budget_are_unpooled():
    pool = PinnedBufferPool(max_bytes=8192, pin=False)
    big = pool.acquire(1 << 20)
    assert pool.stats["overflow_allocs"] == 1
    big.release()
    assert pool.stats["retained_bytes"] <= 8192
    pool.close()


def test_pool_default_keeps_a_jobs_slabs_for_the_next():
    """Under its default cap the pool keeps every slab it allocates: a
    second job of the same shape reuses them all, and classes used one
    after another each keep their slab instead of reallocating per job."""
    pool = PinnedBufferPool(pin=False)
    assert pool.max_bytes == POOL_BYTES
    job = [pool.acquire(1 << 20) for _ in range(3)]
    cls = job[0].capacity
    for b in job:
        b.release()
    small = pool.acquire(1000)      # one class after another, as staging
    small.release()
    assert pool.stats["retained_bytes"] == 3 * cls + small.capacity
    again = [pool.acquire(1 << 20) for _ in range(3)]
    again.append(pool.acquire(1000))
    assert pool.stats["reuses"] == 4 and pool.stats["allocs"] == 4
    assert pool.stats["overflow_allocs"] == 0
    for b in again:
        b.release()
    pool.close()


# ---------------------------------------------------------------------------
# byte-budget admission
# ---------------------------------------------------------------------------
def test_byte_budget_blocks_submit_until_completion(tmp_path):
    _write_file(tmp_path / "blob", 1 << 20)
    eng = IOEngine(backend="aio", max_bytes_in_flight=256 * 1024)
    fd = os.open(tmp_path / "blob", os.O_RDONLY)
    try:
        tickets = [eng.submit(fd, 0, 200 * 1024, key=f"k{i}")
                   for i in range(4)]  # forces budget waits past the first
        for t in tickets:
            assert np.asarray(t.wait(10.0)).nbytes == 200 * 1024
            t.release()
        assert eng.snapshot()["budget_waits"] >= 1
        assert eng.bytes_in_flight() == 0
    finally:
        os.close(fd)
        eng.close()


def test_oversized_request_admitted_alone_no_wedge(tmp_path):
    _write_file(tmp_path / "blob", 1 << 20)
    eng = IOEngine(backend="aio", max_bytes_in_flight=64 * 1024)
    fd = os.open(tmp_path / "blob", os.O_RDONLY)
    try:
        t = eng.submit(fd, 0, 1 << 20, key="huge")  # > whole budget
        assert np.asarray(t.wait(10.0)).nbytes == 1 << 20
        t.release()
    finally:
        os.close(fd)
        eng.close()


def test_idle_callback_fires_on_drain(tmp_path):
    _write_file(tmp_path / "blob", 65536)
    eng = IOEngine(backend="aio")
    fired = threading.Event()
    eng.add_idle_callback(fired.set)
    fd = os.open(tmp_path / "blob", os.O_RDONLY)
    try:
        t = eng.submit(fd, 0, 65536, key="k")
        t.wait(5.0)
        t.release()
        assert fired.wait(5.0)
    finally:
        os.close(fd)
        eng.close()


# ---------------------------------------------------------------------------
# depth planning (scheduler knob -> graph metadata)
# ---------------------------------------------------------------------------
def test_plan_read_depth_scales_with_read_share():
    from repro.core.scheduler import plan_read_depth

    # read-dominated prep: deep queue
    assert plan_read_depth([1.0] * 8, [0.1] * 8) == 8
    # transform/stage-dominated: shallow
    assert plan_read_depth([0.1] * 8, [1.0] * 8) == 1
    # no reads at all: depth 1
    assert plan_read_depth([], [1.0]) == 1
    # interference scales the read column up
    d1 = plan_read_depth([0.5] * 4, [1.0] * 4, io_interference=1.0)
    d2 = plan_read_depth([0.5] * 4, [1.0] * 4, io_interference=3.0)
    assert d2 >= d1
    # clamp
    assert plan_read_depth([100.0], [0.001], max_depth=4) == 4


def test_plan_read_depth_roundtrips_through_json():
    from repro.core.scheduler import Choice, Plan

    p = Plan([Choice("k", False)], [0], [], 0.0, read_depth=5)
    q = Plan.from_dict(p.to_dict())
    assert q.read_depth == 5
    # pre-PR plan.json (no read_depth key) loads at the sync-era default
    d = p.to_dict()
    del d["read_depth"]
    assert Plan.from_dict(d).read_depth == 1


def test_compile_plan_stamps_depth_on_read_tasks():
    from repro.core.scheduler import Choice, Plan
    from repro.executor.graph import compile_plan

    order = ["a", "b", "c"]
    plan = Plan([Choice("k", False)] * 3, [0], [[1], [2]], 0.0,
                read_depth=6)
    g = compile_plan(order, plan, weighted={n: True for n in order},
                     use_cache={n: False for n in order})
    for t in g.tasks:
        if t.kind == "read":
            assert t.depth == 6
        else:
            assert t.depth == 1
    # explicit override wins over the plan's
    g2 = compile_plan(order, plan, weighted={n: True for n in order},
                      use_cache={n: False for n in order}, read_depth=2)
    assert all(t.depth == 2 for t in g2.tasks if t.kind == "read")


# ---------------------------------------------------------------------------
# store-level extent reads (super + bundle), CRC drop ladder
# ---------------------------------------------------------------------------
def _store_with_layers(tmp_path, fmt):
    store = LayerStore(tmp_path / fmt, fmt=fmt)
    rng = np.random.default_rng(0)
    want = {}
    for i in range(4):
        w = {"w": rng.standard_normal((64, 64)).astype(np.float32),
             "b": rng.standard_normal((64,)).astype(np.float32)}
        store.write_raw(f"l{i}", w)
        want[f"l{i}"] = w
    if fmt == "super":
        store._super(flush_all=True)
    return store, want


@pytest.mark.parametrize("fmt", ["super", "bundle"])
@pytest.mark.parametrize("backend", available_backends())
def test_submit_read_raw_matches_sync(tmp_path, fmt, backend):
    store, want = _store_with_layers(tmp_path, fmt)
    assert store.supports_async
    eng = IOEngine(backend=backend)
    try:
        handles = {n: store.submit_read_raw(eng, n) for n in want}
        for n, w in want.items():
            got = handles[n].wait(10.0)
            for k, v in w.items():
                assert np.array_equal(np.asarray(got[k]), v), (n, k)
            handles[n].release()
    finally:
        eng.close()
        store.close()


def test_npy_store_stays_sync(tmp_path):
    store, want = _store_with_layers(tmp_path, "npy")
    assert not store.supports_async
    eng = IOEngine(backend="sync")
    try:
        h = store.submit_read_raw(eng, "l0")   # immediate-read shim
        got = h.wait()
        assert np.array_equal(np.asarray(got["w"]), want["l0"]["w"])
    finally:
        eng.close()


def test_async_corrupt_cache_extent_drops_and_reports(tmp_path):
    from repro.checkpoint.superbundle import read_super_header

    store, want = _store_with_layers(tmp_path, "super")
    store.write_cached("l0", "k", {"w": np.ones((8, 8), np.float32)})
    store._super(flush_all=True)
    store.close()
    ent = read_super_header(store._super_path)["layers"]["l0"]["cache"]["k"][0]
    with open(store._super_path, "r+b") as f:
        f.seek(ent["offset"] + 5)
        f.write(b"\xff\xff\xff")
    eng = IOEngine(backend="aio")
    try:
        h = store.submit_read_cached(eng, "l0", "k")
        assert h.wait(10.0) == {}  # dropped, like the sync audit
        assert any(d.get("layer") == "l0"
                   and "checksum" in d.get("reason", "")
                   for d in store.dropped_entries)
        # raw side of the same layer still reads clean
        h2 = store.submit_read_raw(eng, "l0")
        got = h2.wait(10.0)
        assert np.array_equal(np.asarray(got["w"]), want["l0"]["w"])
        h2.release()
    finally:
        eng.close()
        store.close()


# ---------------------------------------------------------------------------
# fault injection at the engine sites: bounded retries, typed faults,
# nothing leaked at shutdown
# ---------------------------------------------------------------------------
def test_bundle_close_leaves_fd_to_in_flight_reads(tmp_path, monkeypatch):
    """Closing a super-bundle while the engine still owes reads on its fd
    defers the close to the last of them: the reads land clean bytes, never
    EBADF or another file's bytes, and the fd closes once they finish."""
    store, want = _store_with_layers(tmp_path, "super")
    gate = threading.Event()
    real = iomod._read_fully

    def gated(req):
        gate.wait(10.0)
        return real(req)

    eng = IOEngine(backend="aio")   # its self-check reads ungated
    monkeypatch.setattr(iomod, "_read_fully", gated)
    try:
        pend = store.submit_read_raw(eng, "l0")
        sb = pend.sb
        store.close()               # every read is still behind the gate
        assert sb._fd is not None
        with pytest.raises(RuntimeError, match="closed bundle"):
            sb.submit_read(eng, "l1")
        gate.set()
        got = pend.wait(10.0)
        for k, v in want["l0"].items():
            assert np.array_equal(np.asarray(got[k]), v)
        pend.release()
        assert eng.drain(10.0)
        assert sb._fd is None
    finally:
        gate.set()
        eng.close()


@pytest.mark.parametrize("site", ["ioengine.submit", "ioengine.reap"])
def test_injected_engine_fault_is_typed_and_retryable(tmp_path, site):
    store, want = _store_with_layers(tmp_path, "super")
    inj = FaultInjector(seed=3, rates={site: 1.0}, max_faults_per_key=1)
    store.fault_injector = inj
    eng = IOEngine(backend="aio")
    try:
        # per-extent keys each fault at most once (max_faults_per_key=1),
        # so a bounded number of retries always clears the chaos — the
        # same guarantee the pool's RetryPolicy leans on. The executor's
        # read task retries the whole submit+wait op, so the test does too.
        got, faults, h = None, 0, None
        for _ in range(6):
            try:
                if h is None:
                    h = store.submit_read_raw(eng, "l0")
                got = h.wait(10.0)
                break
            except ReadFault:
                faults += 1   # handle self-reset: next attempt resubmits
        assert got is not None and faults >= 1
        for k, v in want["l0"].items():
            assert np.array_equal(np.asarray(got[k]), v)
        h.release()
        assert inj.injected and inj.injected[0]["site"] == site
        # a faulted attempt's abandoned reads may still be on a backend
        # worker; nothing leaks once they finish
        assert eng.drain(10.0)
        snap = eng.snapshot()
        assert snap["in_flight"] == 0 and snap["bytes_in_flight"] == 0
    finally:
        store.fault_injector = None
        eng.close()
        store.close()


def test_cold_run_survives_engine_site_chaos(tmp_path):
    """End-to-end: chaos at both engine sites, pool-level bounded retries
    clear every injected fault, output bit-identical to the quiet run."""
    from repro.core.engine import ColdEngine
    from repro.models.cnn import build_cnn

    layers, x = build_cnn("mobilenet", image=16, width=0.25)
    eng = ColdEngine(layers, tmp_path / "s", store_fmt="super",
                     shader_cache=False)
    eng.decide(x, n_little=2)
    y0 = np.asarray(eng.run_cold(x, n_little=2).output)
    inj = FaultInjector(seed=11, rates={"ioengine.submit": 0.3,
                                        "ioengine.reap": 0.3},
                        max_faults_per_key=1)
    eng.fault_injector = inj
    eng.store.fault_injector = inj
    eng.retry_policy = RetryPolicy(max_attempts=4, backoff_s=0.0)
    eng._runtimes.clear()
    try:
        y1 = np.asarray(eng.run_cold(x, n_little=2).output)
    finally:
        eng.fault_injector = None
        eng.store.fault_injector = None
    assert inj.injected, "chaos must actually fire to prove anything"
    np.testing.assert_array_equal(y0, y1)
    io_eng = get_io_engine()
    snap = io_eng.snapshot()
    assert snap["in_flight"] == 0 and snap["bytes_in_flight"] == 0


def test_engine_close_leaks_nothing(tmp_path):
    _write_file(tmp_path / "blob", 65536)
    before = {t.name for t in threading.enumerate()}
    eng = IOEngine(backend="aio")
    fd = os.open(tmp_path / "blob", os.O_RDONLY)
    t = eng.submit(fd, 0, 65536, key="k")
    t.wait(5.0)
    t.release()
    os.close(fd)
    eng.close()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        after = {t.name for t in threading.enumerate()} - before
        if not any(n.startswith("repro-") for n in after):
            break
        time.sleep(0.05)
    leaked = [n for n in ({t.name for t in threading.enumerate()} - before)
              if n.startswith("repro-")]
    assert not leaked, f"engine threads leaked past close(): {leaked}"


# ---------------------------------------------------------------------------
# async reads racing a crashing compaction
# ---------------------------------------------------------------------------
def test_async_reads_race_crashing_commit_then_compaction(tmp_path):
    """Reads in flight against the container keep serving correct bytes
    while a journaled cache commit crashes mid-slot-write (torn bytes on
    disk); recovery rolls the tear back, a real compaction then moves
    every live extent, and the next async sweep is still byte-identical."""
    import repro.checkpoint.superbundle as sbmod
    from repro.checkpoint.superbundle import InjectedCrash, set_cache_entry

    store, want = _store_with_layers(tmp_path, "super")
    store.write_cached("l1", "k", {"w": np.ones((32, 32), np.float32)})
    store._super(flush_all=True)
    eng = IOEngine(backend="aio")
    try:
        pend = {n: store.submit_read_raw(eng, n) for n in want}

        def hook(phase, **ctx):
            if phase != "slot":
                return
            f, off = ctx["file"], ctx["offset"]
            payload = ctx["payload"]
            f.seek(off)
            f.write(payload[: len(payload) // 2])   # torn slot write
            f.flush()
            raise InjectedCrash(phase)

        store.close()   # release the reader; commits mutate in place
        sbmod._crash_hook = hook
        try:
            with pytest.raises(InjectedCrash):
                set_cache_entry(store._super_path, "l1", "k",
                                {"w": np.full((32, 32), 0.5, np.float32)})
        finally:
            sbmod._crash_hook = None
        # in-flight reads against the old fd still reap clean bytes
        for n, w in want.items():
            got = pend[n].wait(10.0)
            for k, v in w.items():
                assert np.array_equal(np.asarray(got[k]), v), (n, k)
            pend[n].release()
        # recovery (reopen) drops the torn commit; compaction relocates
        # every live extent; a fresh async sweep is byte-identical
        store.maintain()
        for n, w in want.items():
            h = store.submit_read_raw(eng, n)
            got = h.wait(10.0)
            for k, v in w.items():
                assert np.array_equal(np.asarray(got[k]), v), (n, k)
            h.release()
    finally:
        eng.close()
        store.close()


# ---------------------------------------------------------------------------
# readahead coverage stats (satellite: silent-no-op fix)
# ---------------------------------------------------------------------------
def test_store_readahead_reports_coverage(tmp_path):
    store, want = _store_with_layers(tmp_path, "super")
    try:
        store.readahead(list(want))
        st = store.readahead_stats
        assert st is not None
        assert st["layers_requested"] == len(want)
        if st["madvise_available"]:
            assert st["layers_hinted"] == len(want)
            assert st["bytes_hinted"] > 0
        else:  # the old silent no-op now reports itself
            assert st["layers_hinted"] == 0
    finally:
        store.close()


def test_run_result_carries_readahead_stats(tmp_path):
    from repro.core.engine import ColdEngine
    from repro.models.cnn import build_cnn

    layers, x = build_cnn("mobilenet", image=16, width=0.25)
    eng = ColdEngine(layers, tmp_path / "s", store_fmt="super",
                     shader_cache=False)
    eng.decide(x, n_little=2)
    res = eng.run_cold(x, n_little=2)
    assert res.readahead is not None and res.readahead["mode"] == "engine"
    assert res.readahead["layers_hinted"] >= 1
    assert res.readahead["bytes_hinted"] > 0
    seq = eng.run_cold(x, mode="sequential")
    assert seq.readahead is not None and seq.readahead["mode"] == "madvise"


# ---------------------------------------------------------------------------
# stage engine
# ---------------------------------------------------------------------------
def test_stage_engine_host_matches_stage_weights():
    from repro.core.staging import stage_weights

    w = {"a": np.arange(16, dtype=np.float32).reshape(4, 4)}
    se = StageEngine(backend="host")
    got = se.stage(w)
    ref = stage_weights(w)
    assert np.array_equal(np.asarray(got["a"]), np.asarray(ref["a"]))
    assert se.stats["staged"] == 1
    se.close()


def test_stage_engine_stages_readonly_views():
    se = StageEngine(backend="host")
    a = np.arange(16, dtype=np.float32)
    a.flags.writeable = False   # what ReadTicket.wait hands back
    got = se.stage({"a": a})
    assert np.array_equal(np.asarray(got["a"]),
                          np.arange(16, dtype=np.float32))
    se.close()


# ---------------------------------------------------------------------------
# ProfileDB approximate shape-class matching (satellite)
# ---------------------------------------------------------------------------
def test_profile_db_approx_exact_first_then_sibling(tmp_path):
    from repro.core.profiler import OpProfile, ProfileDB
    from repro.core.registry import (
        LayerSpec, shape_class_key, shape_class_sibling_key,
    )

    spec = LayerSpec("l", "linear", {"in_features": 8, "out_features": 8},
                     {"w": (8, 8)})
    k1 = shape_class_key(spec, input_shape=(1, 8), input_dtype="float32")
    k4 = shape_class_key(spec, input_shape=(4, 8), input_dtype="float32")
    sib1 = shape_class_sibling_key(spec, input_shape=(1, 8),
                                   input_dtype="float32")
    sib4 = shape_class_sibling_key(spec, input_shape=(4, 8),
                                   input_dtype="float32")
    assert k1 != k4 and sib1 == sib4   # siblings: same up to batch dim

    db = ProfileDB(tmp_path / "db.json")
    p = OpProfile(layer="l", kernel="direct", read_raw_s=1.0,
                  transform_s=0.1, read_cached_s=0.5, exec_s=0.01,
                  compile_s=0.0, raw_bytes=256, transformed_bytes=256)
    db.put(k1, "direct", p, sibling_key=sib1)
    # exact miss without approx
    assert db.get(k4, "direct", sibling_key=sib4) is None
    # approx fans the batch-1 profile out to batch 4
    got = db.get(k4, "direct", sibling_key=sib4, approx=True)
    assert got is not None and got.read_raw_s == 1.0
    assert db.stats["approx_hits"] == 1
    # exact entries always win over siblings
    p2 = OpProfile(layer="l", kernel="direct", read_raw_s=9.0,
                   transform_s=0.1, read_cached_s=0.5, exec_s=0.01,
                   compile_s=0.0, raw_bytes=256, transformed_bytes=256)
    db.put(k4, "direct", p2, sibling_key=sib4)
    assert db.get(k4, "direct", sibling_key=sib4,
                  approx=True).read_raw_s == 9.0
    # sibling index survives a save/load cycle
    db.save()
    db2 = ProfileDB(tmp_path / "db.json")
    assert db2.get(shape_class_key(
        spec, input_shape=(16, 8), input_dtype="float32"), "direct",
        sibling_key=sib1, approx=True) is not None


def test_batch_dim_changes_but_feature_dims_do_not_sibling():
    from repro.core.registry import LayerSpec, shape_class_sibling_key

    spec = LayerSpec("l", "linear", {"in_features": 8, "out_features": 8},
                     {"w": (8, 8)})
    a = shape_class_sibling_key(spec, input_shape=(1, 8),
                                input_dtype="float32")
    b = shape_class_sibling_key(spec, input_shape=(1, 16),
                                input_dtype="float32")
    assert a != b   # non-batch dims still separate classes
    assert shape_class_sibling_key(
        LayerSpec("r", "stateless"), input_shape=(1, 8),
        input_dtype="float32") is None


# ---------------------------------------------------------------------------
# ColdServer: byte-budget admission + idle-tick compaction
# ---------------------------------------------------------------------------
def test_server_byte_budget_and_idle_compaction(tmp_path):
    from repro.executor.server import ColdServer
    from repro.models.cnn import build_cnn

    srv = ColdServer(tmp_path / "srv", max_concurrent_preps=2,
                     max_read_bytes_in_flight=8 << 20,
                     idle_compaction_min_interval_s=0.0)
    layers, x = build_cnn("mobilenet", image=16, width=0.25)
    srv.add_model("m0", layers, store_fmt="super", shader_cache=False)
    srv.decide("m0", x)
    y0 = np.asarray(srv.cold_start("m0", x).result().output)
    assert srv.io_engine.max_bytes_in_flight == 8 << 20
    # leave dead extents, then let the engine's idle edge compact them
    st = srv.engines["m0"].store
    st.write_cached("scratch_l", "k", {"w": np.ones((64, 64), np.float32)})
    st._super(flush_all=True)
    st.drop_cached("scratch_l", "k")
    st._super(flush_all=True)
    assert st._super().reclaimable_bytes() > 0
    y1 = np.asarray(srv.cold_start("m0", x).result().output)
    deadline = time.monotonic() + 10.0
    while (time.monotonic() < deadline
           and srv.stats["idle_compactions"] == 0):
        time.sleep(0.05)
    assert srv.stats["idle_compactions"] >= 1
    assert srv.stats["idle_compaction_bytes"] > 0
    np.testing.assert_array_equal(y0, y1)
    # a post-compaction cold start still reads the compacted container
    y2 = np.asarray(srv.cold_start("m0", x).result().output)
    np.testing.assert_array_equal(y0, y2)
    h = srv.health()
    assert h["io_engine"]["in_flight"] == 0
