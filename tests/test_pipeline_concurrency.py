"""Pipelined-runtime concurrency invariants.

Every task is joined before ``RunResult`` is built, so every trace is
complete and stable the moment ``run`` returns; staging is idempotent —
each layer is staged exactly once even under work stealing and read
prefetch across runs; and the sequential baseline's read
ops pay the real disk cost (``mmap=False``) instead of deferring it into
transform/stage through a lazy mmap view.
"""
import time

import numpy as np
import pytest


@pytest.fixture(scope="module")
def tiny_engine(tmp_path_factory):
    from repro.core.engine import ColdEngine
    from repro.models.cnn import build_cnn

    layers, x = build_cnn("mobilenet", image=16, width=0.25)
    eng = ColdEngine(layers, tmp_path_factory.mktemp("conc_store"))
    eng.decide(x, n_little=2)
    return eng, x


def test_each_layer_staged_exactly_once_across_runs(tiny_engine):
    """Work stealing + read prefetch must never produce a duplicate (or
    missing) 'stage' op for a layer, run after run."""
    eng, x = tiny_engine
    weighted = {l.spec.name for l in eng.layers if l.spec.weight_shapes}
    for _ in range(5):
        rt = eng.make_runtime(n_little=2)
        res = rt.run(np.asarray(x, dtype=np.float32), eng.plan)
        counts = {}
        for t in res.traces:
            if t.kind == "stage":
                counts[t.layer] = counts.get(t.layer, 0) + 1
        assert counts == {n: 1 for n in weighted}, counts


def test_traces_complete_when_run_returns(tiny_engine):
    """Every task is joined before RunResult is constructed: no trace may
    be appended after ``run`` returns, and every op kind is fully
    accounted for."""
    eng, x = tiny_engine
    rt = eng.make_runtime(n_little=2)
    res = rt.run(np.asarray(x, dtype=np.float32), eng.plan)
    n = len(res.traces)
    time.sleep(0.05)
    assert len(res.traces) == n, "a task appended a trace post-return"
    weighted = {l.spec.name for l in eng.layers if l.spec.weight_shapes}
    by_kind = {}
    for t in res.traces:
        by_kind.setdefault(t.kind, set()).add(t.layer)
    assert by_kind["read"] == weighted
    assert by_kind["stage"] == weighted
    assert by_kind["execute"] == {l.spec.name for l in eng.layers}
    # every stage finished before its layer's execute started
    exec_start = {t.layer: t.start for t in res.traces if t.kind == "execute"}
    for t in res.traces:
        if t.kind == "stage":
            assert t.end <= exec_start[t.layer] + 1e-9


def test_sequential_baseline_reads_materialize(tiny_engine, monkeypatch):
    """run_sequential must read with mmap=False so the baseline's 'read'
    traces carry the real disk cost, not metadata-only mmap setup."""
    eng, x = tiny_engine
    rt = eng.make_runtime(n_little=2)
    calls = []
    real_read = rt.store.read_raw

    def spy(layer, *, mmap=None):
        calls.append(mmap)
        return real_read(layer, mmap=mmap)

    monkeypatch.setattr(rt.store, "read_raw", spy)
    res = rt.run_sequential(np.asarray(x, dtype=np.float32))
    assert calls and all(m is False for m in calls), calls
    read_s = res.stage_seconds().get("read", 0.0)
    assert read_s > 0.0
