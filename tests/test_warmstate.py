"""Peer warm-state transfer: the wire round-trip, CRC integrity and
fallback, the fetch-vs-disk race (bit-identity + journaling), chaos at
the fetch sites, memory-pressure refusal, and the abortable paced read
that keeps a race-losing read from sleeping out the emulated disk."""
import os
import socket
import tempfile
import threading
import time

import numpy as np
import pytest

from repro.core.scheduler import DEFAULT_LINK_BYTES_PER_S, transfer_estimate
from repro.executor.server import ColdServer
from repro.executor.warmstate import PeerFetcher, WarmStateServer
from repro.faults import FaultInjector, FetchFault, TransientFault
from repro.models.cnn import build_cnn


def _mk_server(root, **kw):
    """One ColdServer with 'mnet' registered + decided on the measured
    super-bundle store. build_cnn is seed-deterministic, so every server
    built this way holds bit-identical weights."""
    layers, x = build_cnn("mobilenet", image=16, width=0.25)
    srv = ColdServer(root, n_little=2, max_concurrent_preps=2, **kw)
    srv.add_model("mnet", layers, store_fmt="super")
    srv.decide("mnet", x, n_little=2)
    return srv, x


@pytest.fixture(scope="module")
def donor():
    """Server A: model resident (one completed cold start) + its warm-state
    endpoint, shared by the read-only tests in this module."""
    root = tempfile.mkdtemp(prefix="warmstate_donor_")
    srv, x = _mk_server(root)
    ref = np.asarray(srv.cold_start("mnet", x).result().output)
    warm = WarmStateServer(srv)
    yield srv, warm, x, ref
    warm.close()


def _peers(warm, resident_bytes=1, link_bytes_per_s=1e9):
    """A peer the cost model will always arm against: tiny advertised
    state over a fast link beats any local plan estimate. (The decline
    branch is exercised explicitly in test_slow_peer_declined.)"""
    return [{"host": warm.host, "port": warm.port,
             "resident_bytes": resident_bytes,
             "link_bytes_per_s": link_bytes_per_s}]


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------
def test_transfer_estimate_units():
    assert transfer_estimate(200_000_000, 200e6) == pytest.approx(1.0)
    assert transfer_estimate(100_000_000, 200e6, rtt_s=0.25) == \
        pytest.approx(0.75)
    # bw<=0 means "unknown link": falls back to the default, never div/0
    assert transfer_estimate(DEFAULT_LINK_BYTES_PER_S, 0.0) == \
        pytest.approx(1.0)
    assert transfer_estimate(0, 200e6) == 0.0


# ---------------------------------------------------------------------------
# wire round-trip
# ---------------------------------------------------------------------------
def test_fetch_roundtrip_bit_identical(donor):
    srv, warm, _, _ = donor
    state, reason = srv.resident_state_for_transfer("mnet")
    assert reason == "ok" and state
    pf = PeerFetcher("mnet", [(warm.host, warm.port)])
    try:
        for lname, kv in state.items():
            got = pf.fetch(lname)
            assert set(got) == set(kv)
            for k, v in kv.items():
                np.testing.assert_array_equal(got[k], np.asarray(v))
    finally:
        pf.close()
    assert pf.stats["layers_fetched"] == len(state)
    assert pf.stats["bytes_fetched"] > 0
    assert pf.stats["crc_failures"] == 0


def test_stream_delivers_every_layer(donor):
    srv, warm, _, _ = donor
    state, _ = srv.resident_state_for_transfer("mnet")
    landed, errs = {}, []
    done = threading.Event()

    pf = PeerFetcher("mnet", [(warm.host, warm.port)])
    try:
        def on_layer(name, kv):
            landed[name] = kv
            if len(landed) == len(state):
                done.set()

        assert pf.start_stream(on_layer, on_error=errs.append)
        # idempotent: the second call must not start a second drain
        assert not pf.start_stream(on_layer, on_error=errs.append)
        assert done.wait(10.0), f"stream delivered {len(landed)} layers"
    finally:
        pf.close()
    assert not errs
    assert set(landed) == set(state)


def test_fetch_unknown_model_raises_typed():
    with pytest.raises(FetchFault):
        # nothing listens here: connect fails as a typed, catchable fault
        PeerFetcher("ghost", [("127.0.0.1", 1)], timeout_s=2.0).fetch("l0")


# ---------------------------------------------------------------------------
# the race, end to end (two servers, one process)
# ---------------------------------------------------------------------------
def test_race_bit_identical_and_journaled(donor, tmp_path):
    _, warm, _, ref = donor
    srv_b, x = _mk_server(tmp_path)
    ticket = srv_b.cold_start("mnet", x, peers=_peers(warm))
    out = np.asarray(ticket.result().output)
    np.testing.assert_array_equal(out, ref)
    assert srv_b.stats["peer_races"] == 1
    events = ticket.job.job.fault_events
    ends = [e for e in events if e.get("action") == "fetch_race_end"]
    assert len(ends) == 1, "every race journals exactly one summary"
    assert ends[0]["crc_failures"] == 0 and ends[0]["refused"] == 0
    # the done-callback folded the outcome into the server's counters
    assert srv_b.stats["peer_layers_fetched"] == ends[0]["layers_fetched"]
    assert srv_b.stats["peer_bytes_fetched"] == ends[0]["bytes_fetched"]


def test_slow_peer_declined(donor, tmp_path):
    """The cost model declines the race when the transfer estimate loses
    to the local plan: no fetcher is built, no session hits the donor."""
    _, warm, _, ref = donor
    srv_b, x = _mk_server(tmp_path)
    sessions = warm.stats["sessions"]
    slow = [{"host": warm.host, "port": warm.port,
             "resident_bytes": 1 << 40, "link_bytes_per_s": 1e3}]
    out = np.asarray(srv_b.cold_start("mnet", x, peers=slow)
                     .result().output)
    np.testing.assert_array_equal(out, ref)
    assert srv_b.stats["peer_races"] == 0
    assert srv_b.stats["peer_races_declined"] == 1
    assert warm.stats["sessions"] == sessions


def test_crc_corruption_falls_back_bit_identical(donor, tmp_path):
    """A corrupted chunk must surface as a typed integrity failure on the
    fetching side and NEVER into the weights: the cold start falls back to
    its local chains and still produces the bit-identical output."""
    _, warm, _, ref = donor
    srv_b, x = _mk_server(tmp_path)
    warm.corrupt_chunks = 2
    try:
        ticket = srv_b.cold_start("mnet", x, peers=_peers(warm))
        out = np.asarray(ticket.result().output)
    finally:
        warm.corrupt_chunks = 0
    np.testing.assert_array_equal(out, ref)
    assert srv_b.stats["peer_crc_failures"] >= 1
    events = ticket.job.job.fault_events
    assert any(e.get("action") == "fetch_fallback" for e in events)


def test_injected_fetch_fault_falls_back_no_leaks(donor, tmp_path):
    """Chaos at the warmstate.fetch site: every delivery faults, the
    stream falls back, the local chains win — and nothing leaks (the
    engine drains, a follow-up cold start still completes)."""
    _, warm, _, ref = donor
    srv_b, x = _mk_server(tmp_path)
    eng = srv_b.engines["mnet"]
    eng.fault_injector = FaultInjector(
        seed=3, rates={"warmstate.fetch": 1.0}, max_faults_per_key=None)
    try:
        ticket = srv_b.cold_start("mnet", x, peers=_peers(warm))
        out = np.asarray(ticket.result().output)
    finally:
        eng.fault_injector = None
    np.testing.assert_array_equal(out, ref)
    events = ticket.job.job.fault_events
    assert any(e.get("action") == "fetch_fallback" for e in events)
    if srv_b.io_engine is not None:
        assert srv_b.io_engine.drain(10.0), "reads leaked after the race"
    # the pool survived the race + fallback: serve again, bit-identical
    out2 = np.asarray(
        srv_b.cold_start("mnet", x, peers=_peers(warm)).result().output)
    np.testing.assert_array_equal(out2, ref)


def test_refusal_under_memory_pressure(donor):
    srv, warm, _, _ = donor
    total = srv.budget.total
    srv.budget.total = 1          # any resident state is now over budget
    srv.budget.charge("test:pressure", 2)
    try:
        state, reason = srv.resident_state_for_transfer("mnet")
        assert state is None and "pressure" in reason
        pf = PeerFetcher("mnet", [(warm.host, warm.port)])
        try:
            with pytest.raises(TransientFault):
                pf.fetch("conv0")
        finally:
            pf.close()
        assert pf.stats["refused"] == 1
    finally:
        srv.budget.total = total
        srv.budget.release("test:pressure")
    state, reason = srv.resident_state_for_transfer("mnet")
    assert reason == "ok" and state


def test_packed_device_arrays_served_bit_identical(tmp_path):
    """Packed params registered as device arrays cross to the host only
    when a peer fetches them: bit-identical, dtype (bf16 too) and shape
    kept, ``total_bytes`` their ``nbytes``, and each fetch counted once
    per array in ``packed_host_copies``/``packed_host_bytes``."""
    import jax
    import jax.numpy as jnp

    from repro.executor.frontdoor import recv_msg, send_msg

    srv, x = _mk_server(tmp_path)
    srv.cold_start("mnet", x).result()
    rng = np.random.default_rng(7)
    packed = {
        "blk0/w": jnp.asarray(rng.standard_normal((64, 48)), jnp.bfloat16),
        "blk0/norm": jnp.asarray(rng.standard_normal(48), jnp.float32),
        "blk1/q": jnp.asarray(rng.integers(-128, 128, (16, 8)), jnp.int8),
    }
    srv.register_packed_state("mnet", packed)
    assert all(isinstance(v, jax.Array)
               for v in srv._packed_state["mnet"].values())
    n_arrays = len(packed)
    n_bytes = sum(int(v.nbytes) for v in packed.values())
    resident, _ = srv.resident_state_for_transfer("mnet")
    state_bytes = sum(int(a.nbytes) for kv in resident.values()
                      for a in kv.values())
    assert srv.stats["packed_host_copies"] == 0    # packed=False: none
    warm = WarmStateServer(srv)
    try:
        # the accept frame's total_bytes, on the raw wire
        with socket.create_connection((warm.host, warm.port),
                                      timeout=10) as sock:
            send_msg(sock, {"type": "fetch", "model": "mnet",
                            "layers": None, "packed": True})
            accept = recv_msg(sock)
            assert accept["type"] == "accept"
            assert accept["total_bytes"] == state_bytes + n_bytes
            while recv_msg(sock)["type"] != "done":
                pass
        assert srv.stats["packed_host_copies"] == n_arrays
        assert srv.stats["packed_host_bytes"] == n_bytes
        pf = PeerFetcher("mnet", [(warm.host, warm.port)])
        try:
            got = pf.fetch_packed()
        finally:
            pf.close()
    finally:
        warm.close()
    assert set(got) == set(packed)
    for k, v in packed.items():
        assert got[k].dtype == v.dtype and got[k].shape == v.shape
        np.testing.assert_array_equal(got[k].view(np.uint8),
                                      np.asarray(v).view(np.uint8))
    assert srv.stats["packed_host_copies"] == 2 * n_arrays
    assert srv.stats["packed_host_bytes"] == 2 * n_bytes


# ---------------------------------------------------------------------------
# abortable paced reads (the race-loser's slot is freed promptly)
# ---------------------------------------------------------------------------
def test_interrupt_unblocks_paced_read(tmp_path):
    from repro.ioengine import IOEngine, ReadAbandoned

    payload = os.urandom(1 << 20)
    p = tmp_path / "blob"
    p.write_bytes(payload)
    eng = IOEngine()
    try:
        # 100 KB/s: the 1 MB read owes ~10s of simulated device time
        eng.set_sim_read_bandwidth(100_000)
        fd = os.open(p, os.O_RDONLY)
        try:
            t = eng.submit(fd, 0, len(payload), key="blob")
            threading.Timer(0.1, t.interrupt).start()
            t0 = time.monotonic()
            with pytest.raises(ReadAbandoned):
                t.wait(5.0)
            assert time.monotonic() - t0 < 2.0, \
                "interrupt did not unblock the paced wait promptly"
            t.release()
        finally:
            os.close(fd)
        # pacing off: the same read completes and the bytes are intact
        eng.set_sim_read_bandwidth(None)
        fd = os.open(p, os.O_RDONLY)
        try:
            t2 = eng.submit(fd, 0, len(payload), key="blob2")
            assert bytes(t2.wait(10.0)) == payload
            t2.release()
        finally:
            os.close(fd)
        assert eng.drain(5.0)
    finally:
        eng.close()
