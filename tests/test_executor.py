"""Executor subsystem: task graphs, the persistent CorePool, ColdServer.

Covers the PR-5 invariants: plan ↔ task-graph equivalence (one shared
representation), zero per-run thread creation on the steady path, work
stealing under a persistent pool across back-to-back runs, and two models
cold-starting concurrently without cross-talk in traces or weights.
"""
import time

import numpy as np
import pytest

from repro.core.scheduler import (
    Choice, LayerCandidates, Plan, pick_steal_donor, schedule, simulate,
)
from repro.executor.graph import TaskGraph, compile_plan, simulate_graph
from repro.executor.pool import CorePool, get_core_pool


# ---------------------------------------------------------------------------
# graph ↔ plan equivalence
# ---------------------------------------------------------------------------
def _random_cands(n, rng, kernels=("a", "b")):
    cands = []
    for i in range(n):
        opts = []
        for k in kernels:
            pl, pb, ex = rng.uniform(0.5, 3.0, 3)
            opts.append((Choice(k, False), float(pl), float(pb), float(ex)))
        cands.append(LayerCandidates(layer=f"l{i}", options=opts))
    return cands


def test_compiled_graph_simulates_identically_to_plan():
    """compile_plan must preserve exactly the structure the scheduler's
    simulator models: big preps, lane queues, exec order."""
    rng = np.random.default_rng(0)
    for trial in range(5):
        cands = _random_cands(8, rng)
        plan = schedule(cands, M_l=2)
        order = [c.layer for c in cands]
        chosen = [next(o for o in c.options if o[0] == ch)
                  for c, ch in zip(cands, plan.choices)]
        pl = [o[1] for o in chosen]
        pb = [o[2] for o in chosen]
        ex = [o[3] for o in chosen]
        graph = compile_plan(order, plan,
                             weighted={n: True for n in order},
                             use_cache={n: False for n in order})
        mk_plan, bd_plan = simulate(pl, pb, ex, plan.big_prep,
                                    plan.little_queues)
        mk_graph, bd_graph = simulate_graph(graph, order, pl, pb, ex)
        assert mk_graph == pytest.approx(mk_plan, abs=1e-12)
        assert bd_graph == bd_plan
        # structure recovery round-trips
        idx = {n: i for i, n in enumerate(order)}
        assert [idx[n] for n in graph.big_prep_layers()] == plan.big_prep
        queues = graph.lane_queues()
        assert [[idx[n] for n in queues.get(j, [])]
                for j in range(len(plan.little_queues))] == \
            [list(q) for q in plan.little_queues]


def test_graph_typed_tasks_and_deps():
    plan = Plan(choices=[Choice("k", False), Choice("k", True)],
                big_prep=[0], little_queues=[[1]], est_makespan=0.0)
    g = compile_plan(["x", "y"], plan,
                     weighted={"x": True, "y": True},
                     use_cache={"x": False, "y": True})
    # raw chain: read -> transform -> stage; cached chain skips transform
    assert [t.kind for t in g.tasks if t.layer == "x" and t.kind != "execute"] \
        == ["read", "transform", "stage"]
    assert [t.kind for t in g.tasks if t.layer == "y" and t.kind != "execute"] \
        == ["read", "stage"]
    ex_x = g.task("x", "execute")
    ex_y = g.task("y", "execute")
    assert g.task("x", "stage").tid in ex_x.deps
    assert ex_x.tid in ex_y.deps and g.task("y", "stage").tid in ex_y.deps
    assert g.task("x", "read").affinity == "big"
    assert g.task("y", "read").affinity == "little" \
        and g.task("y", "read").lane == 0
    g.validate()


def test_pick_steal_donor_rule():
    remaining = {0: ["a", "b"], 1: ["c"], 2: []}
    costs = {"a": 1.0, "b": 1.0, "c": 5.0}
    assert pick_steal_donor(remaining, costs.get) == 1
    assert pick_steal_donor({0: [], 1: []}, costs.get) is None


# ---------------------------------------------------------------------------
# pool semantics (synthetic graphs — no engine, fast)
# ---------------------------------------------------------------------------
@pytest.fixture()
def pool():
    p = CorePool(n_big=1, n_little=2, name="test")
    yield p
    p.shutdown()


def _prep_graph(layers_per_lane, *, cost=lambda n: 1.0, sleep=0.01,
                log=None):
    """A prep-only graph: one read task per layer, per-lane queues."""
    g = TaskGraph()
    for lane, layers in enumerate(layers_per_lane):
        for name in layers:
            def fn(name=name):
                time.sleep(sleep)
                if log is not None:
                    log.append(name)
            g.add(name, "read", affinity="little", lane=lane,
                  cost=cost(name), fn=fn)
    return g

def test_work_stealing_under_persistent_pool_two_runs(pool):
    """An idle little worker must steal the TAIL of the most loaded lane,
    run after run, on the same pool threads."""
    for run in range(2):
        log = []
        g = _prep_graph([["a1", "a2", "a3", "a4"], ["b1"]], log=log,
                        sleep=0.02)
        steals0 = pool.steals
        job = pool.submit(g, name=f"run{run}")
        job.wait(10)
        assert pool.steals > steals0, "no steal happened"
        # the thief (lane-1 worker, done after b1) took a tail 'a' layer
        a_cores = {t.layer: t.core for t in job.traces
                   if t.layer.startswith("a")}
        assert "little1" in a_cores.values(), a_cores
        assert len(job.traces) == 5
    assert pool.threads_created == 3  # 1 big + 2 little, created once


def test_no_thread_creation_on_steady_path(pool):
    g1 = _prep_graph([["a"], ["b"]])
    pool.submit(g1, name="warmup").wait(10)
    before = pool.threads_created
    for _ in range(3):
        g = _prep_graph([["a"], ["b"]])
        pool.submit(g, name="steady").wait(10)
    assert pool.threads_created == before


def test_per_job_trace_accounting(pool):
    """Two jobs in flight: each job's traces contain exactly its own tasks,
    timed against its own clock."""
    g1 = _prep_graph([["x1", "x2"]], sleep=0.02)
    g2 = _prep_graph([[], ["y1", "y2"]], sleep=0.02)
    j1 = pool.submit(g1, name="j1", allow_steal=False)
    j2 = pool.submit(g2, name="j2", allow_steal=False)
    j1.wait(10), j2.wait(10)
    assert {t.layer for t in j1.traces} == {"x1", "x2"}
    assert {t.layer for t in j2.traces} == {"y1", "y2"}
    for j in (j1, j2):
        assert all(t.end >= t.start >= 0.0 for t in j.traces)


def test_failing_task_cancels_job_not_pool(pool):
    g = TaskGraph()
    g.add("l", "read", affinity="little", lane=0,
          fn=lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    g.add("l", "stage", affinity="any", deps=(0,), fn=lambda: None)
    job = pool.submit(g, name="bad")
    with pytest.raises(RuntimeError, match="boom"):
        job.wait(10)
    # pool still serves subsequent jobs
    ok = pool.submit(_prep_graph([["z"]]), name="after")
    ok.wait(10)
    assert {t.layer for t in ok.traces} == {"z"}


def test_preps_done_callback_fires_on_failure_and_late_registration(pool):
    """Admission slots must never leak: preps-done fires even when a prep
    task fails, and a callback registered after the prep phase ended runs
    immediately."""
    g = TaskGraph()
    g.add("l", "read", affinity="little", lane=0,
          fn=lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    fired = []
    job = pool.submit(g, name="failing")
    job.add_preps_callback(lambda j: fired.append("fail"))
    with pytest.raises(RuntimeError):
        job.wait(10)
    deadline = time.time() + 2.0
    while len(fired) < 1 and time.time() < deadline:
        time.sleep(0.005)
    assert fired == ["fail"]
    # late registration on a finished job
    ok = pool.submit(_prep_graph([["z"]]), name="late")
    ok.wait(10)
    ok.add_preps_callback(lambda j: fired.append("late"))
    assert fired == ["fail", "late"]
    # prep-free jobs count as preps-done from the start
    g3 = TaskGraph()
    g3.add("l", "execute", affinity="big", fn=lambda: None)
    j3 = pool.submit(g3, name="prepfree")
    j3.add_preps_callback(lambda j: fired.append("prepfree"))
    assert fired[-1] == "prepfree"
    j3.wait(10)


def test_empty_and_unbound_graphs(pool):
    job = pool.submit(TaskGraph(), name="empty")
    job.wait(1)
    g = TaskGraph()
    g.add("l", "read", affinity="big")       # fn never bound
    with pytest.raises(ValueError, match="no bound fn"):
        pool.submit(g)


# ---------------------------------------------------------------------------
# engine-level: the steady path through the real pipeline
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_engine(tmp_path_factory):
    from repro.core.engine import ColdEngine
    from repro.models.cnn import build_cnn

    layers, x = build_cnn("mobilenet", image=16, width=0.25)
    eng = ColdEngine(layers, tmp_path_factory.mktemp("exec_store"))
    eng.decide(x, n_little=2)
    return eng, x


def test_cold_runs_create_no_threads_after_warmup(tiny_engine):
    eng, x = tiny_engine
    eng.run_cold(x, n_little=2)          # warm-up may grow the pool
    pool = get_core_pool()
    before = pool.threads_created
    r1 = eng.run_cold(x, n_little=2)
    r2 = eng.run_cold(x, n_little=2)
    assert pool.threads_created == before
    np.testing.assert_array_equal(np.asarray(r1.output),
                                  np.asarray(r2.output))
    # runtime object is reused, not rebuilt per call
    assert eng._runtime(n_little=2, work_stealing=True) is \
        eng._runtime(n_little=2, work_stealing=True)


def test_graph_hook_extends_job(tiny_engine):
    """Extra tasks appended via graph_hook (the LLM bridge's mechanism) run
    on the pool, record their kind, and gate job completion."""
    eng, x = tiny_engine
    seen = []

    def hook(graph, weights, lock):
        for t in [t for t in graph.tasks if t.kind == "execute"]:
            graph.add(t.layer, "pack", affinity="any", deps=(t.tid,),
                      fn=lambda name=t.layer: seen.append(name))

    job = eng.submit_cold(x, n_little=2, graph_hook=hook)
    res = job.result(30)
    assert set(seen) == {l.spec.name for l in eng.layers}
    packs = [t for t in res.traces if t.kind == "pack"]
    assert len(packs) == len(eng.layers)
    ex_end = {t.layer: t.end for t in res.traces if t.kind == "execute"}
    for t in packs:
        assert t.start >= ex_end[t.layer] - 1e-9


# ---------------------------------------------------------------------------
# satellite: big/little core pinning (sched_setaffinity, clean no-op fallback)
# ---------------------------------------------------------------------------
def test_cpuset_split_big_top_little_bottom(monkeypatch):
    import os

    from repro.executor import pool as pool_mod

    monkeypatch.setattr(pool_mod, "_HAS_AFFINITY", True)
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: {0, 1, 2, 3},
                        raising=False)
    p = CorePool(n_big=2, n_little=3, name="cpuset", pin_cores=True)
    try:
        # top half of the allowed cores -> big lanes, bottom half -> little;
        # worker indices wrap within their half
        assert p._cpuset_for("big", 0) == {2}
        assert p._cpuset_for("big", 1) == {3}
        assert p._cpuset_for("big", 2) == {2}
        assert p._cpuset_for("little", 0) == {0}
        assert p._cpuset_for("little", 1) == {1}
        assert p._cpuset_for("little", 2) == {0}
        # big and little cpu sets never overlap
        bigs = p._cpuset_for("big", 0) | p._cpuset_for("big", 1)
        littles = p._cpuset_for("little", 0) | p._cpuset_for("little", 1)
        assert not (bigs & littles)
    finally:
        p.shutdown()


def test_workers_pin_on_entry_and_record(monkeypatch):
    import os
    import threading

    from repro.executor import pool as pool_mod

    pins = {}

    def fake_set(pid, cpus):
        pins[threading.current_thread().name] = set(cpus)

    monkeypatch.setattr(pool_mod, "_HAS_AFFINITY", True)
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: {0, 1, 2, 3},
                        raising=False)
    monkeypatch.setattr(os, "sched_setaffinity", fake_set, raising=False)
    p = CorePool(n_big=1, n_little=2, name="pin", pin_cores=True)
    try:
        p.submit(_prep_graph([["a"], ["b"]]), name="warm").wait(10)
        # every spawned worker pinned itself and recorded the outcome
        assert p.pinned and all(v is not None for v in p.pinned.values())
        for tname, cpus in pins.items():
            assert p.pinned[tname] == sorted(cpus)
    finally:
        p.shutdown()


def test_pinning_is_clean_noop_without_affinity_api(monkeypatch):
    from repro.executor import pool as pool_mod

    monkeypatch.setattr(pool_mod, "_HAS_AFFINITY", False)
    p = CorePool(n_big=1, n_little=2, name="nopin", pin_cores=True)
    try:
        job = p.submit(_prep_graph([["a"], ["b"]]), name="run")
        job.wait(10)
        assert job.error is None
        # outcome recorded as "not pinned", nothing raised anywhere
        assert p.pinned and all(v is None for v in p.pinned.values())
    finally:
        p.shutdown()
