"""Typed task graphs — one representation shared by the plan and the runtime.

A scheduling ``Plan`` (kernel/cache choices + prep placement) compiles into
an explicit DAG of typed tasks:

  * per weighted layer, a *prep chain* ``read [→ transform] → stage`` whose
    tasks carry the lane (little core index) or big-core affinity the plan
    assigned, plus the layer's estimated prep cost (the work stealer's
    donor metric);
  * per layer, an ``execute`` task on the big cores, depending on the
    layer's ``stage`` and the previous layer's ``execute`` (the exec chain);
  * optionally, per weighted layer, a dep-free ``fetch_remote`` task
    (affinity ``any``) that races the local prep chain by streaming the
    layer's staged weights from a sibling worker — first finisher wins,
    the loser is cancelled (``CorePool.cancel_tasks``).  ``fetch_remote``
    is deliberately NOT a ``PREP_KINDS`` member: prep accounting
    (admission slots, preps-done, steal metrics) describes the *local*
    chain, and a fetch win retires that chain through cancellation;
  * arbitrary extra tasks (e.g. the LLM bridge's decode-path ``pack`` ops)
    can be appended with explicit deps before submission.

``simulate_graph`` maps a compiled graph back onto the scheduler's
event-driven ``simulate`` — the plan's makespan model and the executor run
the *same* structure, which the equivalence tests pin down.

Spans: every timed step of a cold start is one ``OpTrace`` in its job's
``traces``, opened with ``span``. An open span is also a
``jax.profiler.TraceAnnotation`` named ``nnv12.<kind>``, so a running
profiler puts it on the device trace's clock. Compiles (JAX's trace,
lowering and backend-compile durations) become ``compile`` spans of the
job whose thread ran them; garbage collections become ``gc`` spans on the
absolute ``perf_counter`` clock (``collections_between``).
"""
from __future__ import annotations

import collections
import contextlib
import gc
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Deque, Dict, List, Optional, Sequence, \
    Tuple

from jax.profiler import TraceAnnotation

from repro.core.scheduler import Plan, simulate

# task kinds that count as "preparation" (admission control + accounting)
PREP_KINDS = ("read", "transform", "stage")

#: affinity tags: ``big`` (big-core workers), ``little`` (the lane's little
#: worker, stealable), ``any`` (whoever idles first — warm-state fetch
#: markers, background packing)
AFFINITIES = ("big", "little", "any")


@dataclass
class OpTrace:
    """One span. ``start``/``end`` are seconds on its job's clock
    (``t0``); a ``gc`` span not yet claimed by a job is on the absolute
    ``perf_counter`` clock."""
    layer: str
    kind: str
    core: str
    start: float
    end: float
    request: Optional[int] = None   # the job's ``seq``: one per cold start
    parent: Optional[str] = None    # kind of the enclosing span
    detail: str = ""                # a compile's stage and program; a
    #                                 collection's generation and count; a
    #                                 layer kind's unit's op type and kind


# -- spans -------------------------------------------------------------------
# per thread, the open spans: (job, kind, layer); kind None is a scope
# that only names the job (``serving``)
_tls = threading.local()
# JAX's durations that make a compile span, and the stage each names
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
# the process's latest collections (``gc`` spans, absolute clock)
_gc_spans: Deque[OpTrace] = collections.deque(maxlen=4096)
_gc_open: List[Tuple[float, TraceAnnotation]] = []
_hooks_lock = threading.Lock()
_hooks_on = False


def _frames() -> list:
    frames = getattr(_tls, "frames", None)
    if frames is None:
        frames = _tls.frames = []
    return frames


def _on_compile(event: str, secs: float, fun_name: str = "", **_) -> None:
    stage = _COMPILE_EVENTS.get(event)
    frames = getattr(_tls, "frames", None)
    if stage is None or not frames or frames[-1][0] is None:
        return
    job, kind, layer = frames[-1]
    end = time.perf_counter() - job.t0
    job.traces.append(OpTrace(
        layer, "compile", threading.current_thread().name, end - secs, end,
        request=job.seq, parent=kind, detail=f"{stage} {fun_name}"))


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    if phase == "start":
        ann = TraceAnnotation("nnv12.gc", generation=info["generation"])
        ann.__enter__()
        _gc_open.append((time.perf_counter(), ann))
    elif _gc_open:
        start, ann = _gc_open.pop()
        ann.__exit__(None, None, None)
        _gc_spans.append(OpTrace(
            "", "gc", threading.current_thread().name, start,
            time.perf_counter(), detail=f"gen{info['generation']} "
            f"collected {info['collected']}"))


def _install_hooks() -> None:
    """Once per process: JAX's compile durations and the collector's
    callbacks feed spans."""
    global _hooks_on
    with _hooks_lock:
        if _hooks_on:
            return
        import jax

        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        gc.callbacks.append(_on_gc)
        _hooks_on = True


def collections_between(lo: float, hi: float) -> List[OpTrace]:
    """The ``gc`` spans that overlap [lo, hi] (absolute clock)."""
    return [s for s in list(_gc_spans) if s.end > lo and s.start < hi]


@contextlib.contextmanager
def serving(job: Any):
    """Work this thread does inside belongs to ``job``: spans opened
    without a job, and compiles, are recorded into it."""
    frames = _frames()
    frames.append((job, None, ""))
    try:
        yield
    finally:
        frames.pop()


class span:
    """One span of ``kind`` on the calling thread, as a context manager.

    While open it is the profiler annotation ``nnv12.<kind>`` (``layer``
    and ``request`` as metadata; with no profiler running the annotation
    only checks for one) and the parent of spans and compiles that start
    on this thread. On exit it appends an ``OpTrace`` to its job's
    ``traces`` unless ``record`` is false. ``job`` (anything with
    ``seq``, ``t0`` and ``traces``) defaults to the enclosing span's; with
    none the span is only an annotation. ``start``/``end``/``seconds``
    hold its absolute times once closed."""

    __slots__ = ("kind", "layer", "detail", "job", "record", "parent",
                 "start", "end", "_ann")

    def __init__(self, kind: str, *, job: Any = None, layer: str = "",
                 detail: str = "", record: bool = True):
        self.kind, self.layer, self.detail = kind, layer, detail
        self.job, self.record = job, record

    def __enter__(self) -> "span":
        if not _hooks_on:
            _install_hooks()
        frames = _frames()
        top = frames[-1] if frames else (None, None, "")
        if self.job is None:
            self.job = top[0]
        self.parent = top[1]
        frames.append((self.job, self.kind, self.layer))
        meta = {"layer": self.layer}
        if self.job is not None:
            meta["request"] = self.job.seq
        if self.detail:
            meta["detail"] = self.detail
        self._ann = TraceAnnotation(f"nnv12.{self.kind}", **meta)
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self._ann.__exit__(None, None, None)
        _frames().pop()
        job = self.job
        if self.record and job is not None:
            job.traces.append(OpTrace(
                self.layer, self.kind, threading.current_thread().name,
                self.start - job.t0, self.end - job.t0, request=job.seq,
                parent=self.parent, detail=self.detail))

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclass
class Task:
    tid: int
    layer: str
    kind: str                       # read | transform | stage | execute | ...
    affinity: str                   # big | little | any
    lane: Optional[int] = None      # little lane for affinity == "little"
    deps: Tuple[int, ...] = ()
    cost: float = 0.0               # est. seconds; chain head carries the
                                    # layer's full prep cost (steal metric)
    fn: Optional[Callable[[], None]] = None
    deadline_s: Optional[float] = None  # per-task deadline; None = inherit
                                        # the job-level default (pool watchdog)
    detail: str = ""                # its span's detail
    depth: int = 1                  # read tasks: planned I/O queue depth —
                                    # how many lane successors to submit
                                    # alongside this read (Plan.read_depth)


class TaskGraph:
    def __init__(self):
        self.tasks: List[Task] = []
        self._index: Dict[Tuple[str, str], int] = {}

    def add(self, layer: str, kind: str, *, affinity: str,
            lane: Optional[int] = None, deps: Sequence[int] = (),
            cost: float = 0.0, fn: Optional[Callable] = None) -> Task:
        assert affinity in AFFINITIES, affinity
        if affinity == "little" and lane is None:
            # a laneless little task would sit in a queue no worker drains
            # and no steal reaches — the job would hang forever
            raise ValueError(
                f"little-affinity task {layer}/{kind} needs a lane")
        t = Task(tid=len(self.tasks), layer=layer, kind=kind,
                 affinity=affinity, lane=lane, deps=tuple(deps), cost=cost,
                 fn=fn)
        self.tasks.append(t)
        self._index[(layer, kind)] = t.tid
        return t

    def task(self, layer: str, kind: str) -> Optional[Task]:
        tid = self._index.get((layer, kind))
        return None if tid is None else self.tasks[tid]

    def lanes(self) -> List[int]:
        return sorted({t.lane for t in self.tasks
                       if t.affinity == "little" and t.lane is not None})

    def validate(self) -> None:
        """Deps must point backwards (the builder emits topological order)."""
        for t in self.tasks:
            for d in t.deps:
                if not (0 <= d < t.tid):
                    raise ValueError(
                        f"task {t.tid} ({t.layer}/{t.kind}) has forward or "
                        f"dangling dep {d}")

    # -- plan-structure recovery (simulation / introspection) ---------------
    def exec_order(self) -> List[str]:
        return [t.layer for t in self.tasks if t.kind == "execute"]

    def prep_chains(self) -> Dict[str, List[Task]]:
        """Per-layer prep chain (read/transform/stage tasks, tid order)."""
        chains: Dict[str, List[Task]] = {}
        for t in self.tasks:
            if t.kind in PREP_KINDS:
                chains.setdefault(t.layer, []).append(t)
        return chains

    def big_prep_layers(self) -> List[str]:
        seen, out = set(), []
        for t in self.tasks:
            if t.kind in PREP_KINDS and t.affinity == "big" \
                    and t.layer not in seen:
                seen.add(t.layer)
                out.append(t.layer)
        return out

    def lane_queues(self) -> Dict[int, List[str]]:
        queues: Dict[int, List[str]] = {}
        for t in self.tasks:
            if t.kind in PREP_KINDS and t.affinity == "little":
                q = queues.setdefault(t.lane, [])
                if t.layer not in q:
                    q.append(t.layer)
        return queues


def compile_plan(
    order: Sequence[str],
    plan: Plan,
    *,
    weighted: Dict[str, bool],
    use_cache: Dict[str, bool],
    prep_costs: Optional[Dict[str, float]] = None,
    read_depth: Optional[int] = None,
    fetch_layers: Optional[Sequence[str]] = None,
) -> TaskGraph:
    """Compile a scheduling ``Plan`` into a typed task graph.

    ``weighted`` marks layers with on-disk weights (weightless/stateless
    units get only an ``execute`` task, like the runtime always treated
    them). The ``stage`` op is the tail of the prep chain, on the same
    core.

    ``read_depth`` (default: the plan's) stamps every read task with the
    I/O queue depth the async engine should sustain — the runtime's read
    op submits that many lane successors before reaping its own layer.

    ``fetch_layers`` names weighted layers for which a ``fetch_remote``
    race task is also emitted: dep-free, affinity ``any``, placed FIRST
    (lowest tids) so idle workers start the peer stream before local
    chains queue up.  The execute chain keeps its dep on ``stage`` only —
    a fetch win satisfies it by cancelling the stage task, a fetch loss
    or fault leaves the local chain authoritative."""
    prep_costs = prep_costs or {}
    depth = max(1, int(plan.read_depth if read_depth is None else read_depth))
    g = TaskGraph()
    for name in (fetch_layers or ()):
        if weighted.get(name, False):
            g.add(name, "fetch_remote", affinity="any")
    placement: Dict[str, Tuple[str, Optional[int]]] = {}
    for i in plan.big_prep:
        placement[order[i]] = ("big", None)
    for j, q in enumerate(plan.little_queues):
        for i in q:
            placement[order[i]] = ("little", j)

    def emit_chain(name: str):
        aff, lane = placement.get(name, ("big", None))
        cost = float(prep_costs.get(name, 0.0))
        head = g.add(name, "read", affinity=aff, lane=lane, cost=cost)
        head.depth = depth
        prev = head
        if not use_cache.get(name, False):
            prev = g.add(name, "transform", affinity=aff, lane=lane,
                         deps=(prev.tid,))
        g.add(name, "stage", affinity=aff, lane=lane, deps=(prev.tid,))

    # big-core preps first (tid order is the big worker's priority order:
    # the plan's big preps run before the exec chain, as Algorithm 1 lays
    # them out), then the little lanes in queue order, then the exec chain.
    for i in plan.big_prep:
        if weighted.get(order[i], False):
            emit_chain(order[i])
    for q in plan.little_queues:
        for i in q:
            if weighted.get(order[i], False):
                emit_chain(order[i])
    # any weighted layer the plan did not place (defensive): big cores
    for name in order:
        if weighted.get(name, False) and g.task(name, "read") is None:
            placement.setdefault(name, ("big", None))
            emit_chain(name)

    prev_exec: Optional[Task] = None
    for name in order:
        deps = []
        st = g.task(name, "stage")
        if st is not None:
            deps.append(st.tid)
        if prev_exec is not None:
            deps.append(prev_exec.tid)
        prev_exec = g.add(name, "execute", affinity="big", deps=deps)
    g.validate()
    return g


def simulate_graph(
    graph: TaskGraph,
    order: Sequence[str],
    prep_little: Sequence[float],
    prep_big: Sequence[float],
    exec_big: Sequence[float],
    **kw,
) -> Tuple[float, Dict[str, float]]:
    """Deterministic makespan of a compiled graph — recovers the plan
    structure (big preps, lane queues) from the graph's tasks and feeds the
    scheduler's event-driven ``simulate``: proof that the executor and the
    planner model one and the same structure."""
    idx = {n: i for i, n in enumerate(order)}
    big_prep = [idx[n] for n in graph.big_prep_layers()]
    queues = graph.lane_queues()
    lanes = sorted(queues)
    little_queues = [[idx[n] for n in queues[j]] for j in lanes]
    # weightless layers emit no prep chain; account them as (near-zero-cost)
    # big preps so the simulator sees every layer prepared
    placed = set(big_prep) | {i for q in little_queues for i in q}
    big_prep += [i for i in range(len(order)) if i not in placed]
    return simulate(prep_little, prep_big, exec_big, big_prep,
                    little_queues, **kw)
