"""Host-fingerprint drift: a ProfileDB measured under another fingerprint
on the same backend and device kind (same machine after a jax upgrade /
CPU-count change) serves its entries as STALE fallbacks — the cold path
never re-profiles in-line — and the background path
(``ColdEngine.reprofile_stale``, driven by the server's idle tick)
re-measures them off the request path. Entries measured on another backend
or device kind are never adopted: they miss."""
import json

import pytest

from repro.core.engine import ColdEngine
from repro.core.profiler import OpProfile, ProfileDB
from repro.models.cnn import build_cnn

FAKE_HOST = "cafe0123deadbeef"


def _prof(layer="l0", kernel="k"):
    return OpProfile(layer=layer, kernel=kernel, read_raw_s=1.0,
                     transform_s=0.1, read_cached_s=0.5, exec_s=0.2,
                     compile_s=0.3, raw_bytes=100, transformed_bytes=80)


def _drift_db_file(path, device=None):
    """Rewrite a saved DB as if every entry was measured on another host
    (of the same backend and device kind unless ``device`` names another)."""
    raw = json.loads(path.read_text())
    raw["hosts"] = {FAKE_HOST: v for v in raw["hosts"].values()}
    raw["siblings"] = {FAKE_HOST: v for v in raw.get("siblings", {}).values()}
    raw["devices"] = {FAKE_HOST: device or v
                      for v in raw.get("devices", {}).values()}
    path.write_text(json.dumps(raw))


def test_drifted_entries_serve_stale_and_unstale_on_put(tmp_path):
    p = tmp_path / "db.json"
    db = ProfileDB(p)
    db.put("sc1", "k", _prof())
    db.put("sc2", "k", _prof())
    db.save()
    _drift_db_file(p)

    db2 = ProfileDB(p)
    assert db2.entries == {}                      # nothing fresh
    assert db2.drifted_from == FAKE_HOST
    got = db2.get("sc1", "k")
    assert got is not None and got.read_raw_s == 1.0  # stale entry serves
    assert db2.stats["stale_hits"] == 1
    assert db2.stale == {("sc1", "k")}
    assert db2.stale_pending() == [("sc1", "k")]
    # a fresh measurement supersedes the drifted fallback
    db2.put("sc1", "k", _prof())
    assert db2.stale == set()
    assert db2.get("sc1", "k") is not None
    assert db2.stats["hits"] == 1
    # saving keeps the donor host's entries side by side
    db2.save()
    hosts = json.loads(p.read_text())["hosts"]
    assert FAKE_HOST in hosts and db2.host in hosts


@pytest.mark.parametrize("device", ["tpu/TPU v5 lite", "gpu/H100"])
def test_other_device_entries_never_adopted(tmp_path, device):
    """Timings measured on another backend or device kind are not drift of
    this host: they miss, and decide() profiles afresh."""
    p = tmp_path / "db.json"
    db = ProfileDB(p)
    db.put("sc1", "k", _prof())
    db.save()
    _drift_db_file(p, device=device)

    db2 = ProfileDB(p)
    assert db2.drifted_from is None
    assert db2.get("sc1", "k") is None
    assert db2.stats == {"hits": 0, "misses": 1, "approx_hits": 0,
                         "stale_hits": 0}
    assert db2.stale == set()
    # the foreign device's entries survive a save side by side
    db2.put("sc1", "k", _prof())
    db2.save()
    raw = json.loads(p.read_text())
    assert raw["devices"][FAKE_HOST] == device
    assert raw["devices"][db2.host] == db2.device


def test_no_drift_adoption_when_current_host_has_entries(tmp_path):
    p = tmp_path / "db.json"
    db = ProfileDB(p)
    db.put("sc1", "k", _prof())
    db.save()
    # add a second host WITHOUT wiping ours: no drift, no stale serving
    raw = json.loads(p.read_text())
    raw["hosts"][FAKE_HOST] = {"scX": {"k": raw["hosts"][db.host]
                                       ["sc1"]["k"]}}
    p.write_text(json.dumps(raw))
    db2 = ProfileDB(p)
    assert db2.drifted_from is None
    assert db2.get("scX", "k") is None            # other host stays invisible
    assert db2.stats["stale_hits"] == 0


@pytest.fixture
def drifted_engine(tmp_path):
    layers, x = build_cnn("mobilenet", image=16, width=0.25)
    dbp = tmp_path / "shared_db.json"
    eng = ColdEngine(layers, tmp_path / "store_a", profile_db=str(dbp))
    eng.decide(x, n_little=2)
    _drift_db_file(dbp)
    eng2 = ColdEngine(layers, tmp_path / "store_b", profile_db=str(dbp))
    return eng2, x, dbp


def test_decide_serves_stale_without_inline_reprofiling(drifted_engine):
    eng2, x, _ = drifted_engine
    stats = eng2.decide(x, n_little=2)
    # the cold path paid ZERO profiler calls — every class came from the
    # drifted host's measurements, flagged for background refresh
    assert stats["profile_calls"] == 0
    assert stats["profile_db_stale_hits"] > 0
    assert eng2._stale_reps                       # work list populated
    assert eng2.profile_db.stale_pending()


def test_reprofile_stale_refreshes_off_cold_path(drifted_engine):
    eng2, x, dbp = drifted_engine
    eng2.decide(x, n_little=2)
    n_stale = len(eng2._stale_reps)
    # bounded: one class per idle tick
    assert eng2.reprofile_stale(max_classes=1) == 1
    assert len(eng2._stale_reps) == n_stale - 1
    # drain the rest
    while eng2.reprofile_stale(max_classes=1):
        pass
    assert eng2._stale_reps == {}
    assert eng2.profile_db.stale_pending() == []
    assert eng2.repairs.of_kind("reprofile_drift")
    # fresh measurements landed under the CURRENT host fingerprint
    hosts = json.loads(dbp.read_text())["hosts"]
    assert hosts.get(eng2.profile_db.host)
    # a third engine now decides fully fresh: no stale hits at all
    layers, _ = build_cnn("mobilenet", image=16, width=0.25)
    eng3 = ColdEngine(layers, dbp.parent / "store_c", profile_db=str(dbp))
    stats = eng3.decide(x, n_little=2)
    assert stats["profile_db_stale_hits"] == 0
    assert stats["profile_calls"] == 0            # fresh DB hits instead


def test_server_idle_tick_reprofiles_one_class(tmp_path):
    from repro.executor.server import ColdServer

    layers, x = build_cnn("mobilenet", image=16, width=0.25)
    seed = ColdEngine(layers, tmp_path / "seed",
                      profile_db=str(tmp_path / "fd_db.json"))
    seed.decide(x, n_little=2)
    _drift_db_file(tmp_path / "fd_db.json")

    srv = ColdServer(tmp_path / "srv", n_little=2, share_profile_db=True)
    srv.profile_db = ProfileDB(tmp_path / "fd_db.json")
    srv.add_model("mnet", layers)
    srv.decide("mnet", x, n_little=2)
    eng = srv.engines["mnet"]
    assert eng._stale_reps
    before = len(eng._stale_reps)
    srv._idle_tick(["mnet"], 0)                   # one idle tick
    assert srv.stats["idle_reprofiles"] == 1
    assert len(eng._stale_reps) == before - 1     # bounded: one per tick
