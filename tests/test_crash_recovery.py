"""Crash-atomicity of super-bundle (v3/v4) in-place commits.

Covers: CRC-32C correctness (known vectors + reference implementation),
journal record parsing with torn tails, every crash phase of the
journaled in-place commit (after journal fsync / mid-slot / post-slots
pre-header / torn header / pre-commit-record), checksum-triggered drops
under ``verify="lazy"`` and ``verify="eager"``, v2 backward compatibility,
compaction of dead extents, ``LayerStore`` plumbing (``verify=``,
``dropped_entries``, ``maintain``), and the unified header-validation
error text.

The invariant under test: after ANY injected tear, reopening the
container succeeds, raw weights still serve byte-identically, and the
affected cache entry is either fully applied or fully rolled back —
``read_cached`` never returns torn bytes. Format-v4 quantized extents
(int8 / packed int4 + header scale metadata) get the same guarantees: a
torn quantized entry is dropped at open — never served — and recomputing
it from raw yields a bit-identical clean write.
"""
import struct

import numpy as np
import pytest

import repro.checkpoint.superbundle as S
from repro.checkpoint import LayerStore
from repro.checkpoint.bundle import _pad_to
from repro.checkpoint.integrity import crc32c
from repro.checkpoint.superbundle import (
    HEADER_SLACK, InjectedCrash, IntegrityError, SuperBundle, compact,
    drop_cache_entry, journal_path, read_super_header, recover_journal,
    set_cache_entries, set_cache_entry, write_superbundle,
)


# ---------------------------------------------------------------------------
# CRC-32C
# ---------------------------------------------------------------------------
def _crc_ref(data: bytes) -> int:
    """Textbook bytewise CRC-32C (reflected, poly 0x82F63B78)."""
    tab = []
    for i in range(256):
        c = i
        for _ in range(8):
            c = (c >> 1) ^ 0x82F63B78 if c & 1 else c >> 1
        tab.append(c)
    crc = 0xFFFFFFFF
    for b in data:
        crc = (crc >> 8) ^ tab[(crc ^ b) & 0xFF]
    return crc ^ 0xFFFFFFFF


def test_crc32c_known_vectors():
    assert crc32c(b"") == 0
    assert crc32c(b"123456789") == 0xE3069283  # RFC 3720 check value
    assert crc32c(b"The quick brown fox jumps over the lazy dog") == 0x22620404


def test_crc32c_matches_reference_across_block_boundaries():
    rng = np.random.default_rng(0)
    # straddle the vectorized-block boundary (1024) and the bytewise tail
    for n in (1, 63, 1023, 1024, 1025, 2048, 5000):
        d = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
        assert crc32c(d) == _crc_ref(d), n


def test_crc32c_incremental_and_ndarray():
    data = bytes(range(256)) * 20
    assert crc32c(data[100:], crc32c(data[:100])) == crc32c(data)
    a = np.arange(300, dtype=np.float32).reshape(30, 10)
    assert crc32c(a) == crc32c(a.tobytes())


def test_crc32c_fast_path_cross_checks_software():
    """Satellite (PR 5): when a C-backed CRC-32C is importable it serves
    the hot path, and it must agree bit-for-bit with the numpy software
    implementation on every size class and on incremental chaining."""
    from repro.checkpoint.integrity import _crc32c_software, crc32c_backend

    backend = crc32c_backend()
    rng = np.random.default_rng(7)
    for n in (0, 1, 63, 1023, 1024, 4096, 100_000):
        a = rng.integers(0, 256, n, dtype=np.uint8)
        assert crc32c(a) == _crc32c_software(a), (backend, n)
        mid = n // 2
        assert crc32c(a[mid:], crc32c(a[:mid])) == _crc32c_software(a), \
            (backend, n)
    # non-contiguous / non-uint8 arrays route through the same view logic
    f = rng.standard_normal((64, 8)).astype(np.float32)
    assert crc32c(f) == _crc32c_software(f)


def test_crc32c_software_env_override(monkeypatch):
    """REPRO_CRC32C=software must force the fallback (fleet debugging +
    the cross-check harness depend on it)."""
    import repro.checkpoint.integrity as integ

    monkeypatch.setenv("REPRO_CRC32C", "software")
    monkeypatch.setattr(integ, "_FAST", None)
    monkeypatch.setattr(integ, "_FAST_PROBED", False)
    assert integ.crc32c_backend() == "numpy-software"
    assert integ.crc32c(b"123456789") == 0xE3069283


# ---------------------------------------------------------------------------
# crash injection
# ---------------------------------------------------------------------------
def _model():
    return {"a": {"w": np.arange(200, dtype=np.float32)},
            "b": {"q": np.ones(30, np.int8)}}


OLD_CACHE = np.zeros(200, np.float32)
NEW_CACHE = np.full(200, 9.0, np.float32)


def _store(tmp_path, name):
    p = tmp_path / f"{name}.superbundle"
    write_superbundle(p, _model(), order=["a", "b"])
    set_cache_entry(p, "a", "kA", {"w": OLD_CACHE})  # append -> rewrite
    return p


def _crash_commit(p, phase, partial=False):
    """Replace the kA entry in place, crashing at ``phase``. ``partial``
    additionally tears the write itself (half a slot / garbled header)."""
    def hook(ph, **ctx):
        if ph != phase:
            return
        if partial and ph == "slot":
            f, off, payload = ctx["file"], ctx["offset"], ctx["payload"]
            f.seek(off)
            f.write(payload[: len(payload) // 2])
            f.flush()
        if partial and ph == "header":
            f, hdr = ctx["file"], ctx["header"]
            f.seek(0)
            f.write(b"NNVS" + struct.pack("<I", 3) + hdr[:40])
            f.flush()
        raise InjectedCrash(ph)

    S._crash_hook = hook
    try:
        with pytest.raises(InjectedCrash):
            set_cache_entry(p, "a", "kA", {"w": NEW_CACHE})
    finally:
        S._crash_hook = None


def _assert_recovered(p, expect):
    """Reopen with full verification: raw intact, cache entry fully old /
    fully new / dropped, journal drained, compaction leaves zero slack."""
    w = _model()
    with SuperBundle(p, verify="eager") as sb:
        for layer, tensors in w.items():
            got = sb.read_raw(layer, materialize=True)
            for k, v in tensors.items():
                np.testing.assert_array_equal(np.asarray(got[k]), v)
        if expect == "dropped":
            assert not sb.has_cached("a", "kA")
            assert any(d["layer"] == "a" and d["kernel"] == "kA"
                       for d in sb.dropped), sb.dropped
        else:
            assert not sb.dropped, sb.dropped
            want = OLD_CACHE if expect == "old" else NEW_CACHE
            got = np.asarray(sb.read_cached("a", "kA", materialize=True)["w"])
            np.testing.assert_array_equal(got, want)
    assert journal_path(p).stat().st_size == 0  # recovery drained the journal
    compact(p)
    with SuperBundle(p, verify="eager") as sb:
        assert sb.reclaimable_bytes() == 0


def test_crash_after_journal_before_data_keeps_old_entry(tmp_path):
    p = _store(tmp_path, "m")
    _crash_commit(p, "journal-synced")
    _assert_recovered(p, "old")


def test_crash_mid_slot_drops_torn_entry(tmp_path):
    p = _store(tmp_path, "m")
    _crash_commit(p, "slot", partial=True)
    _assert_recovered(p, "dropped")


def test_crash_post_slots_pre_header_rolls_forward(tmp_path):
    p = _store(tmp_path, "m")
    _crash_commit(p, "header")
    _assert_recovered(p, "new")


def test_crash_with_torn_header_restores_from_journal(tmp_path):
    p = _store(tmp_path, "m")
    _crash_commit(p, "header", partial=True)
    # the torn header must be detected before recovery even consults it
    with pytest.raises(ValueError):
        read_super_header(p)
    _assert_recovered(p, "new")


def test_crash_before_commit_record_rolls_forward(tmp_path):
    p = _store(tmp_path, "m")
    _crash_commit(p, "header-written")
    _assert_recovered(p, "new")


def test_torn_journal_tail_is_ignored(tmp_path):
    p = _store(tmp_path, "m")
    with open(journal_path(p), "ab") as f:
        f.write(b"SBJ1B" + struct.pack("<I", 9999) + b"torn")
    _assert_recovered(p, "old")


def test_truncated_journal_record_is_ignored(tmp_path):
    p = _store(tmp_path, "m")
    mid = np.full(200, 5.0, np.float32)
    assert set_cache_entry(p, "a", "kA", {"w": mid}) == "inplace"
    jb = journal_path(p).read_bytes()
    # tear off the COMMIT record's tail: the BEGIN still resolves (its data
    # fully landed) and rolls forward
    journal_path(p).write_bytes(jb[:-7])
    with SuperBundle(p, verify="eager") as sb:
        np.testing.assert_array_equal(
            np.asarray(sb.read_cached("a", "kA", materialize=True)["w"]), mid)
        assert not sb.dropped


def test_recover_journal_is_idempotent(tmp_path):
    p = _store(tmp_path, "m")
    _crash_commit(p, "slot", partial=True)
    first = recover_journal(p)
    assert len(first) == 1 and first[0]["layer"] == "a"
    assert recover_journal(p) == []  # drained: second replay is a no-op
    # the drop is already persisted in the header — later opens see a clean
    # container with no entry and nothing further to report
    with SuperBundle(p, verify="eager") as sb:
        assert not sb.has_cached("a", "kA")
        assert not sb.dropped
        np.testing.assert_array_equal(
            np.asarray(sb.read_raw("a")["w"]), _model()["a"]["w"])


def test_stale_journal_from_old_generation_is_ignored(tmp_path):
    p = _store(tmp_path, "m")
    _crash_commit(p, "journal-synced")  # pending record against gen G
    # a full rewrite supersedes the container (gen G+1) and resets the
    # journal; resurrect the stale record and check it is never replayed
    jb = journal_path(p).read_bytes()
    compact(p)
    journal_path(p).write_bytes(jb)
    _assert_recovered(p, "old")


# ---------------------------------------------------------------------------
# checksum verification without a journal (latent bit-rot)
# ---------------------------------------------------------------------------
def _flip_byte(p, offset):
    with open(p, "r+b") as f:
        f.seek(offset)
        b = f.read(1)
        f.seek(offset)
        f.write(bytes([b[0] ^ 0xFF]))


def test_eager_verify_drops_corrupt_cache_and_raises_on_raw(tmp_path):
    p = _store(tmp_path, "m")
    hdr = read_super_header(p)
    e = hdr["layers"]["a"]["cache"]["kA"][0]
    _flip_byte(p, e["offset"] + 5)
    with SuperBundle(p, verify="eager") as sb:
        assert not sb.has_cached("a", "kA")
        assert sb.dropped[0]["kernel"] == "kA"
        np.testing.assert_array_equal(
            np.asarray(sb.read_raw("a")["w"]), _model()["a"]["w"])

    p2 = _store(tmp_path, "m2")
    hdr = read_super_header(p2)
    _flip_byte(p2, hdr["layers"]["b"]["raw"][0]["offset"])
    with pytest.raises(IntegrityError, match="b/q"):
        SuperBundle(p2, verify="eager")


def test_lazy_verify_drops_on_first_materializing_read(tmp_path):
    p = _store(tmp_path, "m")
    hdr = read_super_header(p)
    e = hdr["layers"]["a"]["cache"]["kA"][0]
    _flip_byte(p, e["offset"] + 5)
    with SuperBundle(p, verify="lazy") as sb:
        assert sb.has_cached("a", "kA")  # not audited yet
        assert sb.read_cached("a", "kA", materialize=True) == {}
        assert not sb.has_cached("a", "kA")
        assert sb.dropped and sb.dropped[0]["kernel"] == "kA"
    with SuperBundle(p, verify="never") as sb:
        # never-mode serves bytes as-is — the caller opted out of auditing
        assert sb.read_cached("a", "kA", materialize=True)["w"].shape == (200,)
    # compaction re-audits and refuses to carry the corrupt entry forward
    stats = compact(p)
    assert stats["dropped"] and stats["dropped"][0]["kernel"] == "kA"


def test_invalid_verify_mode_rejected(tmp_path):
    p = _store(tmp_path, "m")
    with pytest.raises(ValueError, match="never|lazy|eager"):
        SuperBundle(p, verify="sometimes")


# ---------------------------------------------------------------------------
# v2 backward compatibility
# ---------------------------------------------------------------------------
def _write_v2(path, name, arr):
    """Hand-rolled minimal v2 container (no checksums, no generation)."""
    arr = np.ascontiguousarray(arr)
    entry = {"name": name, "dtype": str(arr.dtype), "shape": list(arr.shape),
             "nbytes": int(arr.nbytes)}
    header = {"order": ["l"], "layers": {"l": {"raw": [entry], "cache": {}}}}
    import json
    for _ in range(8):
        hdr = json.dumps(header, separators=(",", ":")).encode()
        off = _pad_to(16 + len(hdr) + HEADER_SLACK)
        if entry.get("offset") == off:
            break
        entry["offset"] = off
    with open(path, "wb") as f:
        f.write(struct.pack("<4sIQ", b"NNVS", 2, len(hdr)))
        f.write(hdr)
        f.write(b"\0" * (off - f.tell()))
        f.write(arr.tobytes())


def test_v2_container_reads_and_upgrades_to_current(tmp_path):
    p = tmp_path / "old.superbundle"
    arr = np.arange(40, dtype=np.float32)
    _write_v2(p, "w", arr)
    with SuperBundle(p, verify="eager") as sb:  # no checksums: nothing fails
        assert sb.version == 2 and sb.generation == 0
        np.testing.assert_array_equal(
            np.asarray(sb.read_raw("l", materialize=True)["w"]), arr)
    # any mutation upgrades via the rewrite path (v2 has no slot checksums,
    # so the journaled in-place commit refuses to run on it)
    assert set_cache_entry(p, "l", "k", {"w": arr}) == "rewrite"
    with SuperBundle(p, verify="eager") as sb:
        assert sb.version == S.VERSION and sb.generation == 1
        assert all("crc32c" in e for e in sb._all_entries("l"))


def test_version_too_new_error_is_consistent(tmp_path):
    p = _store(tmp_path, "m")
    _flip = struct.pack("<I", 99)
    with open(p, "r+b") as f:
        f.seek(4)
        f.write(_flip)
    with pytest.raises(ValueError) as e1:
        read_super_header(p)
    with pytest.raises(ValueError) as e2:
        SuperBundle(p, recover=False)
    # ONE shared validator: identical message, naming the file and both
    # the found and the supported version
    assert str(e1.value) == str(e2.value)
    assert str(p) in str(e1.value)
    assert "99" in str(e1.value) and str(S.VERSION) in str(e1.value)


# ---------------------------------------------------------------------------
# LayerStore plumbing + engine hook
# ---------------------------------------------------------------------------
def test_layerstore_surfaces_dropped_entries_and_raw_survives(tmp_path):
    st = LayerStore(tmp_path, fmt="super")
    for layer, tensors in _model().items():
        st.write_raw(layer, tensors)
    st.write_cached("a", "kA", {"w": OLD_CACHE})
    assert st.cache_bytes() > 0  # flush
    p = tmp_path / "model.superbundle"
    _crash_commit(p, "slot", partial=True)
    st2 = LayerStore(tmp_path, fmt="super")
    np.testing.assert_array_equal(
        np.asarray(st2.read_raw("a", mmap=False)["w"]), _model()["a"]["w"])
    assert any(d["kernel"] == "kA" for d in st2.dropped_entries)
    assert not st2.has_cached("a", "kA")
    # maintain() compacts the dead extent the rolled-back entry left
    stats = st2.maintain()
    assert stats["compacted"] and stats["reclaimed_bytes"] > 0


def test_layerstore_maintain_background(tmp_path):
    st = LayerStore(tmp_path, fmt="super")
    st.write_raw("l", {"w": np.ones(4096, np.float32)})
    st.write_cached("l", "k", {"w": np.ones(4096, np.float32)})
    assert st.cache_bytes() > 0  # flush
    st.drop_cached("l", "k")
    stats = st.maintain(background=True)
    assert stats["compacted"] and stats.get("background")
    real = st.maintain_wait()
    assert real is not None and real["reclaimed_bytes"] > 0
    assert st.maintain_wait() is None  # nothing pending anymore
    with SuperBundle(tmp_path / "model.superbundle") as sb:
        assert sb.reclaimable_bytes() == 0


def test_rewrite_over_existing_container_derives_fresh_generation(tmp_path):
    """A default-generation rewrite (e.g. ``migrate`` onto an existing
    path) must still supersede the old container's generation, so stale
    journal records can never replay against the new file."""
    p = tmp_path / "m.superbundle"
    write_superbundle(p, _model(), order=["a", "b"])
    assert read_super_header(p)["generation"] == 0
    write_superbundle(p, _model(), order=["a", "b"])  # default generation
    assert read_super_header(p)["generation"] == 1
    # and past any pending journal record, even with the header torn
    _crash_commit(_store(tmp_path, "m2"), "journal-synced")
    p2 = tmp_path / "m2.superbundle"
    gen_rec = 1 + int(read_super_header(p2)["generation"])
    with open(p2, "r+b") as f:
        f.write(b"XXXX")  # torn magic: old header unreadable
    write_superbundle(p2, _model(), order=["a", "b"])
    assert read_super_header(p2)["generation"] >= gen_rec


def test_layerstore_harvests_lazy_drops_after_open(tmp_path):
    st = LayerStore(tmp_path, fmt="super")
    for layer, tensors in _model().items():
        st.write_raw(layer, tensors)
    st.write_cached("a", "kA", {"w": OLD_CACHE})
    assert st.cache_bytes() > 0  # flush
    hdr = read_super_header(tmp_path / "model.superbundle")
    _flip_byte(tmp_path / "model.superbundle",
               hdr["layers"]["a"]["cache"]["kA"][0]["offset"] + 3)
    st2 = LayerStore(tmp_path, fmt="super")
    assert st2.read_cached("a", "kA", mmap=False) == {}  # lazy audit drops
    st2.close()  # reader invalidation harvests the post-open drop report
    assert any(d["kernel"] == "kA" for d in st2.dropped_entries)


def test_rewrite_audits_extents_instead_of_restamping(tmp_path):
    """A container rewrite restamps fresh checksums — it must audit the
    bytes it copies forward, or latent bit-rot would be laundered into
    'verified' data. Corrupt cache entries are dropped; corrupt raw
    refuses to rewrite."""
    st = LayerStore(tmp_path, fmt="super")
    for layer, tensors in _model().items():
        st.write_raw(layer, tensors)
    st.write_cached("a", "kA", {"w": OLD_CACHE})
    assert st.cache_bytes() > 0  # flush
    p = tmp_path / "model.superbundle"
    hdr = read_super_header(p)
    _flip_byte(p, hdr["layers"]["a"]["cache"]["kA"][0]["offset"] + 7)
    st2 = LayerStore(tmp_path, fmt="super")
    st2.write_raw("c", {"z": np.ones(8, np.float32)})
    st2.read_raw("c")  # flush -> full rewrite
    with SuperBundle(p, verify="eager") as sb:
        assert not sb.has_cached("a", "kA")  # dropped, not restamped
    assert any(d["kernel"] == "kA" for d in st2.dropped_entries)

    st3 = LayerStore(tmp_path / "rawrot", fmt="super")
    st3.write_raw("a", _model()["a"])
    st3.read_raw("a")  # flush
    p3 = tmp_path / "rawrot" / "model.superbundle"
    _flip_byte(p3, read_super_header(p3)["layers"]["a"]["raw"][0]["offset"])
    st4 = LayerStore(tmp_path / "rawrot", fmt="super")
    st4.write_raw("b", {"q": np.ones(4, np.float32)})
    with pytest.raises(IntegrityError):
        st4.read_raw("b")  # flush must refuse to copy rotten raw forward


def _prep_one_layer(rt):
    """Run the runtime's one-layer plan through its task graph (reads
    through a sync I/O engine) and return the layer's staged weights, as
    ``graph_hook`` sees them."""
    from repro.core.scheduler import Choice, Plan
    from repro.ioengine import IOEngine

    seen = {}

    def hook(graph, weights, lock):
        seen["weights"] = weights

    plan = Plan([Choice(rt.kernels["l"].name, True)], big_prep=[0],
                little_queues=[], est_makespan=0.0)
    rt.io_engine = IOEngine(backend="sync")
    try:
        rt.submit(np.zeros(1, np.float32), plan, graph_hook=hook).result(30)
    finally:
        rt.io_engine.close()
    return seen["weights"]


def test_pipeline_prep_falls_back_when_cache_dropped(tmp_path):
    """A use_cache layer whose entry was dropped (recovery/audit) must be
    re-derived from raw by the runtime, never executed with no weights."""
    from repro.core.pipeline import PipelineRuntime
    from repro.core.registry import LayerSpec

    st = LayerStore(tmp_path, fmt="super")
    raw = {"w": np.arange(8, dtype=np.float32)}
    st.write_raw("l", raw)
    st.read_raw("l")  # flush; NO cache entry exists for kernel "k"

    class Kern:
        name = "k"

        def transform(self, w, spec):
            return {"w": np.asarray(w["w"]) * 2}

    spec = LayerSpec(name="l", op_type="linear",
                     weight_shapes={"w": (8,)})
    rt = PipelineRuntime([spec], {"l": Kern()}, {"l": True}, st,
                         {"l": lambda w, x: x}, n_little=1)
    weights = _prep_one_layer(rt)
    np.testing.assert_array_equal(np.asarray(weights["l"]["w"]),
                                  raw["w"] * 2)


def test_maintain_quiesces_before_new_writes(tmp_path):
    """A mutation (or second maintain) while a background compaction is in
    flight must join it first — two concurrent rewrites would interleave
    into the same tmp file."""
    st = LayerStore(tmp_path, fmt="super")
    st.write_raw("l", {"w": np.ones(4096, np.float32)})
    st.write_cached("l", "k", {"w": np.ones(4096, np.float32)})
    assert st.cache_bytes() > 0  # flush
    st.drop_cached("l", "k")
    assert st.maintain(background=True)["compacted"]
    st.write_cached("l", "k2", {"w": np.zeros(16, np.float32)})  # quiesces
    assert st._maintain_thread is None  # background run was joined
    assert st.cache_bytes() > 0  # flush merges cleanly on top
    with SuperBundle(tmp_path / "model.superbundle", verify="eager") as sb:
        assert sb.has_cached("l", "k2") and not sb.dropped


def test_engine_decide_reports_store_maintenance(tmp_path):
    from repro.core.engine import ColdEngine
    from repro.models.cnn import build_cnn

    layers, x = build_cnn("mobilenet", image=16, width=0.25)
    eng = ColdEngine(layers, tmp_path / "s", store_fmt="super")
    stats = eng.decide(x, n_little=2)
    assert "store_maintenance" in stats
    with SuperBundle(tmp_path / "s" / "model.superbundle") as sb:
        # decide()'s drops/materializations end fully compacted
        assert sb.reclaimable_bytes() == 0
    out = np.asarray(eng.run_cold(x).output)
    assert np.isfinite(out).all()


# ---------------------------------------------------------------------------
# batched multi-entry transactions (PR 6, journal batching)
# ---------------------------------------------------------------------------
OLD_B_CACHE = np.zeros(30, np.int8)
NEW_B_CACHE = np.full(30, 7, np.int8)


def _batch_store(tmp_path):
    p = tmp_path / "batch.superbundle"
    write_superbundle(p, _model(), order=["a", "b"])
    set_cache_entry(p, "a", "kA", {"w": OLD_CACHE})
    set_cache_entry(p, "b", "kB", {"q": OLD_B_CACHE})
    return p


def _crash_batch(p, phase, partial=False):
    """Replace BOTH entries in one transaction, crashing at ``phase``.
    ``partial`` tears the first slot write (entry a) mid-payload."""
    def hook(ph, **ctx):
        if ph != phase:
            return
        if partial and ph == "slot":
            f, off, payload = ctx["file"], ctx["offset"], ctx["payload"]
            f.seek(off)
            f.write(payload[: len(payload) // 2])
            f.flush()
        raise InjectedCrash(ph)

    S._crash_hook = hook
    try:
        with pytest.raises(InjectedCrash):
            set_cache_entries(p, {("a", "kA"): {"w": NEW_CACHE},
                                  ("b", "kB"): {"q": NEW_B_CACHE}})
    finally:
        S._crash_hook = None


def _assert_batch(p, expect_a, expect_b):
    """Per-entry resolution: each entry of the torn batch independently
    ends fully old, fully new, or dropped — never torn."""
    with SuperBundle(p, verify="eager") as sb:
        for layer, tensors in _model().items():
            got = sb.read_raw(layer, materialize=True)
            for k, v in tensors.items():
                np.testing.assert_array_equal(np.asarray(got[k]), v)
        for layer, kernel, tname, old, new, expect in (
                ("a", "kA", "w", OLD_CACHE, NEW_CACHE, expect_a),
                ("b", "kB", "q", OLD_B_CACHE, NEW_B_CACHE, expect_b)):
            if expect == "dropped":
                assert not sb.has_cached(layer, kernel)
                assert any(d["layer"] == layer and d["kernel"] == kernel
                           for d in sb.dropped), sb.dropped
            else:
                want = old if expect == "old" else new
                got = np.asarray(sb.read_cached(
                    layer, kernel, materialize=True)[tname])
                np.testing.assert_array_equal(got, want)
    assert journal_path(p).stat().st_size == 0  # recovery drained it
    compact(p)
    with SuperBundle(p, verify="eager") as sb:
        assert sb.reclaimable_bytes() == 0


def test_batched_crash_after_journal_keeps_both_old(tmp_path):
    p = _batch_store(tmp_path)
    _crash_batch(p, "journal-synced")
    _assert_batch(p, "old", "old")


def test_batched_crash_mid_slot_drops_only_the_torn_entry(tmp_path):
    # entry a's slot is half-written; entry b's bytes were never touched —
    # per-entry resolution must drop a and keep b fully old
    p = _batch_store(tmp_path)
    _crash_batch(p, "slot", partial=True)
    _assert_batch(p, "dropped", "old")


@pytest.mark.parametrize("phase", ["header", "header-written"])
def test_batched_crash_post_slots_rolls_both_forward(tmp_path, phase):
    p = _batch_store(tmp_path)
    _crash_batch(p, phase)
    _assert_batch(p, "new", "new")


def test_batched_refresh_is_one_fsync_pair(tmp_path, monkeypatch):
    """N replacements in one transaction cost ONE journal fsync + ONE
    container fsync; the unbatched path pays a pair per entry."""
    p = _batch_store(tmp_path)
    calls = []
    real = S.fsync_file
    monkeypatch.setattr(S, "fsync_file",
                        lambda f: (calls.append(1), real(f))[1])
    res = set_cache_entries(p, {("a", "kA"): {"w": NEW_CACHE},
                                ("b", "kB"): {"q": NEW_B_CACHE}})
    assert res["mode"] == "inplace"
    assert len(calls) == 2
    _assert_batch(p, "new", "new")
    calls.clear()
    set_cache_entry(p, "a", "kA", {"w": OLD_CACHE})
    set_cache_entry(p, "b", "kB", {"q": OLD_B_CACHE})
    # a pair PER entry (plus journal drains on reopen): strictly worse
    assert len(calls) >= 4


def test_layerstore_flush_batches_cache_refreshes(tmp_path, monkeypatch):
    """The store buffers write_cached() calls; a flush over N existing
    same-shape entries commits them as ONE journaled transaction."""
    st = LayerStore(tmp_path, fmt="super")
    w = {f"l{i}": {"w": np.arange(64, dtype=np.float32) + i}
         for i in range(3)}
    for layer, tensors in w.items():
        st.write_raw(layer, tensors)
        st.write_cached(layer, "k", {"w": tensors["w"] * 2})
    st._super(flush_all=True)  # initial materialization: one rewrite
    calls = []
    real = S.fsync_file
    monkeypatch.setattr(S, "fsync_file",
                        lambda f: (calls.append(1), real(f))[1])
    for layer, tensors in w.items():
        st.write_cached(layer, "k", {"w": tensors["w"] * 3})
    st._super(flush_all=True)  # 3 replacements -> ONE in-place txn
    # one fsync pair for the whole commit + ONE deferred journal drain
    # when the shared reader reopens — constant in N (per-entry commits
    # would cost a pair each, >= 6 here)
    assert len(calls) == 3
    for layer, tensors in w.items():
        np.testing.assert_array_equal(
            np.asarray(st.read_cached(layer, "k", mmap=False)["w"]),
            tensors["w"] * 3)


# ---------------------------------------------------------------------------
# crashes during compaction / background maintenance (PR 6)
# ---------------------------------------------------------------------------
def test_crash_during_compact_preserves_original(tmp_path, monkeypatch):
    """compact() publishes by atomic rename: a crash anywhere before the
    rename leaves the original container untouched and a retry heals."""
    p = _store(tmp_path, "m")
    drop_cache_entry(p, "a", "kA")  # dead extent -> compactable slack

    def crash_write(path, emit, durable=True):
        raise InjectedCrash("compact-rewrite")

    monkeypatch.setattr(S, "atomic_write", crash_write)
    with pytest.raises(InjectedCrash):
        compact(p)
    monkeypatch.undo()
    with SuperBundle(p, verify="eager") as sb:
        np.testing.assert_array_equal(
            np.asarray(sb.read_raw("a", materialize=True)["w"]),
            _model()["a"]["w"])
        assert sb.reclaimable_bytes() > 0  # slack still there, file intact
    stats = compact(p)  # retry succeeds
    assert stats["reclaimed_bytes"] > 0
    with SuperBundle(p, verify="eager") as sb:
        assert sb.reclaimable_bytes() == 0


def test_background_maintain_crash_surfaces_and_store_survives(
        tmp_path, monkeypatch):
    """A compaction failing in the background thread must be re-raised by
    maintain_wait(), never swallowed — and the container it was rewriting
    stays fully serveable."""
    st = LayerStore(tmp_path, fmt="super")
    st.write_raw("l", {"w": np.ones(4096, np.float32)})
    st.write_cached("l", "k", {"w": np.ones(4096, np.float32)})
    assert st.cache_bytes() > 0  # flush
    st.drop_cached("l", "k")  # in-place drop leaves a dead extent

    def crash_write(path, emit, durable=True):
        raise InjectedCrash("bg-compact")

    monkeypatch.setattr(S, "atomic_write", crash_write)
    assert st.maintain(background=True)["compacted"]
    with pytest.raises(InjectedCrash):
        st.maintain_wait()
    monkeypatch.undo()
    np.testing.assert_array_equal(
        np.asarray(st.read_raw("l", mmap=False)["w"]),
        np.ones(4096, np.float32))
    real = st.maintain()  # retry on the intact container heals
    assert real["compacted"] and real["reclaimed_bytes"] > 0


# ---------------------------------------------------------------------------
# quantized cache extents (format v4) under crashes
# ---------------------------------------------------------------------------
def _quant_model():
    rng = np.random.default_rng(21)
    return {"a": {"w": rng.standard_normal((40, 12)).astype(np.float32)},
            "b": {"q": np.ones(30, np.int8)}}


def _quant_entries(weights, seed):
    """Deterministic int4 companions for layer a's weight. seed != 0 adds
    an additive perturbation so old/new PAYLOAD bytes differ (a pure
    rescale would quantize to identical int values and recovery would
    rightly roll forward) while folded shapes stay identical."""
    from repro import quant

    w = weights["a"]["w"]
    if seed:
        rng = np.random.default_rng(100 + seed)
        w = w + rng.standard_normal(w.shape).astype(np.float32)
    return quant.quantize_weight("w", np.asarray(w, np.float32), bits=4)


def _quant_store(tmp_path, name):
    p = tmp_path / f"{name}.superbundle"
    write_superbundle(p, _quant_model(), order=["a", "b"])
    set_cache_entry(p, "a", "int4", _quant_entries(_quant_model(), 0))
    return p


def _crash_quant_commit(p, phase, partial=False):
    def hook(ph, **ctx):
        if ph != phase:
            return
        if partial and ph == "slot":
            f, off, payload = ctx["file"], ctx["offset"], ctx["payload"]
            f.seek(off)
            f.write(payload[: len(payload) // 2])
            f.flush()
        raise InjectedCrash(ph)

    S._crash_hook = hook
    try:
        with pytest.raises(InjectedCrash):
            set_cache_entry(p, "a", "int4",
                            _quant_entries(_quant_model(), 1))
    finally:
        S._crash_hook = None


def _assert_quant_recovered(p, expect):
    w = _quant_model()
    want = _quant_entries(w, 0 if expect == "old" else 1)
    with SuperBundle(p, verify="eager") as sb:
        np.testing.assert_array_equal(
            np.asarray(sb.read_raw("a", materialize=True)["w"]), w["a"]["w"])
        if expect == "dropped":
            # torn int4 extent: dropped at open, NEVER served
            assert not sb.has_cached("a", "int4")
            assert sb.read_cached("a", "int4", materialize=True) == {}
            assert any(d["layer"] == "a" and d["kernel"] == "int4"
                       for d in sb.dropped), sb.dropped
        else:
            got = sb.read_cached("a", "int4", materialize=True)
            assert set(got) == set(want)
            for k in want:
                assert got[k].dtype == want[k].dtype, k
                np.testing.assert_array_equal(np.asarray(got[k]), want[k])
    assert journal_path(p).stat().st_size == 0


@pytest.mark.parametrize("phase,partial,expect", [
    ("journal-synced", False, "old"),
    ("slot", True, "dropped"),
    ("header", False, "new"),
    ("header-written", False, "new"),
])
def test_quantized_extent_crash_phases(tmp_path, phase, partial, expect):
    p = _quant_store(tmp_path, "q")
    _crash_quant_commit(p, phase, partial=partial)
    _assert_quant_recovered(p, expect)


def test_torn_int4_entry_recomputes_from_raw_bit_identical(tmp_path):
    """The degradation ladder's recompute-from-raw: after a torn int4
    extent is dropped, re-running the transform on the (intact) raw bytes
    and committing must produce a container byte-identical in content to
    one that never crashed."""
    p = _quant_store(tmp_path, "q")
    _crash_quant_commit(p, "slot", partial=True)
    _assert_quant_recovered(p, "dropped")
    # ladder recompute: transform(raw) -> write. Quantization is
    # deterministic, so this equals a clean write of the same entry.
    with SuperBundle(p) as sb:
        raw = {k: np.asarray(v, np.float32)
               for k, v in sb.read_raw("a", materialize=True).items()}
    recomputed = _quant_entries({"a": raw}, 1)
    set_cache_entry(p, "a", "int4", recomputed)
    clean = tmp_path / "clean.superbundle"
    write_superbundle(clean, _quant_model(), order=["a", "b"])
    set_cache_entry(clean, "a", "int4", _quant_entries(_quant_model(), 1))
    with SuperBundle(p, verify="eager") as sb, \
            SuperBundle(clean, verify="eager") as sc:
        a = sb.read_cached("a", "int4", materialize=True)
        b = sc.read_cached("a", "int4", materialize=True)
        assert set(a) == set(b) == {"w:q4", "w:qscale"}
        for k in a:
            assert a[k].dtype == b[k].dtype
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]))
        assert not sb.dropped


def test_pipeline_prep_rederives_quantized_entry_after_drop(tmp_path):
    """Runtime rung of the same ladder: a use_cache layer whose quantized
    entry was dropped must be re-derived from raw by the pipeline runtime
    — bit-identical companions, never empty weights."""
    from repro import quant
    from repro.core.pipeline import PipelineRuntime
    from repro.core.registry import LayerSpec, LinearInt4

    st = LayerStore(tmp_path, fmt="super")
    raw = _quant_model()["a"]
    st.write_raw("l", raw)
    st.read_raw("l")  # flush; NO cache entry exists for kernel "int4"

    kern = LinearInt4()
    spec = LayerSpec(name="l", op_type="linear",
                     weight_shapes={"w": raw["w"].shape})
    rt = PipelineRuntime([spec], {"l": kern}, {"l": True}, st,
                         {"l": lambda w, x: x}, n_little=1)
    weights = _prep_one_layer(rt)
    want = kern.transform(dict(raw), spec)
    assert set(weights["l"]) == set(want)
    for k in want:
        np.testing.assert_array_equal(np.asarray(weights["l"][k]),
                                      np.asarray(want[k]))
    assert quant.is_quantized(
        {k: np.asarray(v) for k, v in weights["l"].items()})


def test_readers_race_crashing_compaction_see_only_committed_state(tmp_path):
    """Independent readers hammering the container while a background
    compaction crashes (and then a retry succeeds) must only ever observe
    fully committed state — old or new generation, never torn bytes."""
    import threading

    st = LayerStore(tmp_path, fmt="super")
    raw = {"w": np.arange(4096, dtype=np.float32)}
    st.write_raw("l", raw)
    st.write_cached("l", "k", {"w": raw["w"] * 2})
    st.write_cached("l", "dead", {"w": raw["w"] * 3})
    assert st.cache_bytes() > 0  # flush
    st.drop_cached("l", "dead")  # slack for the compaction to reclaim

    p = tmp_path / "model.superbundle"
    stop = threading.Event()
    errors = []

    def reader():
        while not stop.is_set():
            try:
                with SuperBundle(p, verify="eager") as sb:
                    got = np.asarray(
                        sb.read_raw("l", materialize=True)["w"])
                    if not np.array_equal(got, raw["w"]):
                        errors.append("torn raw bytes")
                    c = sb.read_cached("l", "k", materialize=True)
                    if c and not np.array_equal(np.asarray(c["w"]),
                                                raw["w"] * 2):
                        errors.append("torn cache bytes")
            except Exception as e:  # noqa: BLE001 - collected for assert
                errors.append(repr(e))

    threads = [threading.Thread(target=reader) for _ in range(2)]
    for t in threads:
        t.start()
    try:
        real_write = S.atomic_write

        def crash_write(path, emit, durable=True):
            raise InjectedCrash("bg-compact")

        S.atomic_write = crash_write
        try:
            st.maintain(background=True)
            with pytest.raises(InjectedCrash):
                st.maintain_wait()
        finally:
            S.atomic_write = real_write
        stats = st.maintain(background=True)  # retry, racing the readers
        assert stats["compacted"]
        assert st.maintain_wait()["reclaimed_bytes"] > 0
    finally:
        stop.set()
        for t in threads:
            t.join(10)
    assert errors == []
    with SuperBundle(p, verify="eager") as sb:
        assert sb.reclaimable_bytes() == 0
