"""Packed weight-bundle format + mmap LayerStore + staged pipeline tests.

Covers the cold-path I/O overhaul: bundle round-trips across dtypes
(f32/bf16/int8), 64-byte segment alignment, mmap-view immutability,
LayerStore bundle round-trips, and the pipeline's 'stage' ops (weights arrive on device during prep — no
host->device conversion on the exec chain).
"""
import numpy as np
import jax
import pytest

from repro.checkpoint import LayerStore
from repro.checkpoint.bundle import (
    ALIGN, bundle_nbytes, read_bundle, read_header, write_bundle,
)


def _example_weights():
    import ml_dtypes

    rng = np.random.default_rng(0)
    return {
        "w": rng.standard_normal((17, 33)).astype(np.float32),
        "b": rng.standard_normal(33).astype(np.float32),
        "q8": (rng.standard_normal((5, 9)) * 20).astype(np.int8),
        "hb": rng.standard_normal((12, 8)).astype(np.float32)
              .astype(ml_dtypes.bfloat16),
    }


@pytest.mark.parametrize("mmap", [False, True])
def test_bundle_roundtrip_dtypes(tmp_path, mmap):
    w = _example_weights()
    write_bundle(tmp_path / "l.bundle", w)
    back = read_bundle(tmp_path / "l.bundle", mmap=mmap)
    assert set(back) == set(w)
    for k in w:
        assert back[k].dtype == w[k].dtype, k      # incl. native bf16
        np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(w[k]))


def test_bundle_alignment_and_accounting(tmp_path):
    w = _example_weights()
    total = write_bundle(tmp_path / "l.bundle", w)
    hdr = read_header(tmp_path / "l.bundle")
    offsets = [e["offset"] for e in hdr["tensors"]]
    assert all(o % ALIGN == 0 for o in offsets)
    assert offsets == sorted(offsets)              # sequential layout
    payload = bundle_nbytes(tmp_path / "l.bundle")
    assert payload == sum(v.nbytes for v in w.values())
    assert payload < total == (tmp_path / "l.bundle").stat().st_size


def test_mmap_views_are_immutable(tmp_path):
    w = {"w": np.arange(64, dtype=np.float32)}
    write_bundle(tmp_path / "l.bundle", w)
    view = read_bundle(tmp_path / "l.bundle", mmap=True)["w"]
    assert not view.flags.writeable
    with pytest.raises(ValueError):
        view[0] = 1.0
    # transforms copy, so downstream mutation never corrupts the store
    doubled = np.asarray(view) * 2
    np.testing.assert_array_equal(
        read_bundle(tmp_path / "l.bundle", mmap=True)["w"], w["w"])
    assert doubled[1] == 2.0


def test_bundle_rejects_bad_magic(tmp_path):
    p = tmp_path / "junk.bundle"
    p.write_bytes(b"NOPE" + b"\0" * 64)
    with pytest.raises(ValueError):
        read_bundle(p)


def test_layerstore_dotted_layer_names_do_not_collide(tmp_path):
    """'block.0' and 'block.1' must map to distinct bundle files (a naive
    with_suffix would truncate at the last dot and collide)."""
    st = LayerStore(tmp_path)
    w0 = {"w": np.zeros((2, 2), np.float32)}
    w1 = {"w": np.ones((3, 3), np.float32)}
    st.write_raw("block.0", w0)
    st.write_raw("block.1", w1)
    np.testing.assert_array_equal(np.asarray(st.read_raw("block.0")["w"]),
                                  w0["w"])
    np.testing.assert_array_equal(np.asarray(st.read_raw("block.1")["w"]),
                                  w1["w"])
    assert st.raw_bytes("block.0") > 0 and st.raw_bytes("block.1") > 0


def test_layerstore_cached_bundle_roundtrip_bf16(tmp_path):
    import ml_dtypes

    st = LayerStore(tmp_path)
    w = {"w": np.ones((8, 8), np.float32).astype(ml_dtypes.bfloat16)}
    st.write_cached("l0", "bf16_cast", w)
    assert st.has_cached("l0", "bf16_cast")
    back = st.read_cached("l0", "bf16_cast")
    assert back["w"].dtype == ml_dtypes.bfloat16
    np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(w["w"]))
    assert st.cache_bytes() > 0
    st.drop_cached("l0", "bf16_cast")
    assert not st.has_cached("l0", "bf16_cast")
    assert st.cache_bytes() == 0


def test_stage_weights_never_alias_mmap(tmp_path):
    """CPU XLA zero-copy-aliases aligned host buffers; staging must still
    end with device-resident memory, never file-backed mmap pages (which
    would defer the disk I/O into execute)."""
    from repro.core.staging import stage_weights

    rng = np.random.default_rng(0)
    w = {"w": rng.standard_normal((256, 256)).astype(np.float32)}
    write_bundle(tmp_path / "l.bundle", w)
    view = read_bundle(tmp_path / "l.bundle", mmap=True)
    staged = stage_weights(view)
    assert isinstance(staged["w"], jax.Array)
    assert not np.shares_memory(np.asarray(staged["w"]), view["w"])
    np.testing.assert_array_equal(np.asarray(staged["w"]), w["w"])


@pytest.fixture(scope="module")
def staged_run(tmp_path_factory):
    from repro.core.engine import ColdEngine
    from repro.models.cnn import build_cnn

    layers, x = build_cnn("squeezenet", image=24, width=0.35)
    eng = ColdEngine(layers, tmp_path_factory.mktemp("stage_store"))
    eng.decide(x, n_little=2)
    return eng, eng.run_cold(x)


def test_stage_ops_on_prep_not_exec_chain(staged_run):
    """Every weighted layer is staged by a dedicated 'stage' op; execute ops
    see device-resident weights (no host->device conversion inside them)."""
    eng, res = staged_run
    staged_layers = {t.layer for t in res.traces if t.kind == "stage"}
    weighted = {l.spec.name for l in eng.layers if l.spec.weight_shapes}
    assert staged_layers == weighted
    # stage ops ran on prep cores / off the exec chain, and finished before
    # the layer's execute started
    exec_start = {t.layer: t.start for t in res.traces if t.kind == "execute"}
    for t in res.traces:
        if t.kind == "stage":
            assert t.end <= exec_start[t.layer] + 1e-9
    # resident weights are device arrays, ready for warm reuse
    for name, w in (res.weights or {}).items():
        for v in w.values():
            assert isinstance(v, jax.Array)


def test_sequential_baseline_also_stages(staged_run):
    eng, _ = staged_run
    layers = [l for l in eng.layers]
    x = eng._input_example
    res = eng.run_cold(x, mode="sequential")
    kinds = [t.kind for t in res.traces]
    assert "stage" in kinds
    weighted = sum(1 for l in layers if l.spec.weight_shapes)
    assert sum(1 for k in kinds if k == "execute") == len(layers)


def test_profiles_carry_stage_split(staged_run):
    """The profiler reports the read-vs-stage split the scheduler plans
    against; staged transfer costs are > 0 for weighted layers."""
    eng, _ = staged_run
    for l in eng.layers:
        if not l.spec.weight_shapes:
            continue
        for p in eng.profiles[l.spec.name]:
            assert p.stage_s > 0.0
            assert p.prep_s(False) == pytest.approx(
                p.read_raw_s + p.transform_s + p.stage_s)
            assert p.prep_s(False, include_stage=False) == pytest.approx(
                p.read_raw_s + p.transform_s)


def test_profile_json_roundtrip_with_stage(tmp_path, staged_run):
    from repro.core.profiler import load_profiles, save_profiles

    eng, _ = staged_run
    save_profiles(tmp_path / "p.json", eng.profiles)
    back = load_profiles(tmp_path / "p.json")
    assert back.keys() == eng.profiles.keys()
    any_p = next(iter(back.values()))[0]
    assert hasattr(any_p, "stage_s")
