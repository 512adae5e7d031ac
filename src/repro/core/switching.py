"""Kernel switching for continuous inference — §3.5.

K_cold (the scheduler's choices) can be slower at steady state than K_warm
(fastest-execution kernels). In continuous mode the engine:
  1. runs the cold inference with K_cold as usual;
  2. on idle little-core threads, prepares the kernels in K_warm − K_cold
     (read raw + transform into the warm format, and compile);
  3. switches layer-by-layer: the 2nd inference uses the warm kernel for
     every layer whose preparation finished, pipelining the rest exactly
     like a cold inference (paper: 2nd inference ≈ 8% slower, 3rd equal).
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.core.engine import ColdEngine
from repro.core.pipeline import RunResult, OpTrace
from repro.core.staging import stage
from repro.executor.graph import TaskGraph
from repro.executor.pool import Job


@dataclass
class ContinuousSession:
    engine: ColdEngine
    n_little: int = 3
    warm_weights: Dict[str, Any] = field(default_factory=dict)
    _lock: threading.Lock = field(default_factory=threading.Lock)
    _bg: Optional[Job] = None

    cold_weights: Dict[str, Any] = field(default_factory=dict)

    def cold_infer(self, x) -> RunResult:
        """First inference: K_cold plan + background warm-kernel prep."""
        res = self.engine.run_cold(x, n_little=self.n_little)
        self.cold_weights = {
            k: {k2: jnp.asarray(v2) for k2, v2 in w.items()}
            for k, w in (res.weights or {}).items()
        }
        self._start_background_prep()
        return res

    def _start_background_prep(self):
        """Queue K_warm − K_cold preps as one 'any'-affinity job on the
        persistent pool: idle little workers pick them up between cold
        runs, with no per-call thread creation."""
        eng = self.engine
        warm = eng.warm_best_choices()
        todo = [
            (l, wc) for l, wc, cc in
            zip(eng.layers, warm, eng.plan.choices)
            if wc.kernel != cc.kernel and l.spec.weight_shapes
        ]
        if not todo:
            self._bg = None
            return

        def prep(l, wc):
            def fn():
                kern = eng._kernel_by_name(l.spec, wc.kernel)
                raw = eng.store.read_raw(l.spec.name)
                w = kern.transform(raw, l.spec)
                with self._lock:
                    # stage (not bare jnp.asarray): identity transforms
                    # hand back read-only mmap views, which CPU XLA would
                    # alias — leaving their disk I/O to fault in during
                    # execute
                    self.warm_weights[l.spec.name] = (wc.kernel, stage(w))
            return fn

        g = TaskGraph()
        for l, wc in todo:
            g.add(l.spec.name, "warm_prep", affinity="any", fn=prep(l, wc))
        rt = eng._runtime(n_little=self.n_little, work_stealing=True)
        self._bg = rt._get_pool().submit(g, name="warm-switch")

    def warm_infer(self, x, wait: bool = False) -> RunResult:
        """Subsequent inference: use warm kernels where prepared."""
        eng = self.engine
        if wait and self._bg is not None:
            self._bg.wait()
        t0 = time.perf_counter()
        traces = []
        # weights for layers not yet switched: use the cold plan's kernels
        rt = eng.make_runtime(n_little=self.n_little)
        y = jnp.asarray(x)
        warm = {c.kernel: c for c in eng.warm_best_choices()}
        jitted_warm = eng._jitted_map(eng.warm_best_choices(), eng._input_example)
        jitted_cold = rt.jitted
        for l, cold_choice in zip(eng.layers, eng.plan.choices):
            name = l.spec.name
            with self._lock:
                ready = self.warm_weights.get(name)
            ts = time.perf_counter()
            if ready is not None:
                _, w = ready
                y = jitted_warm[name](w, y)
            elif name in self.cold_weights:
                # unswitched layer: resident K_cold weights from the 1st run
                y = jitted_cold[name](self.cold_weights[name], y)
            else:
                kern = eng._kernel_by_name(l.spec, cold_choice.kernel)
                if cold_choice.use_cache:
                    w = eng.store.read_cached(name, kern.name)
                    if not w and l.spec.weight_shapes:
                        # dropped/torn cache entry: re-derive from raw
                        w = kern.transform(eng.store.read_raw(name), l.spec)
                else:
                    w = kern.transform(eng.store.read_raw(name), l.spec) \
                        if l.spec.weight_shapes else {}
                y = jitted_cold[name](stage(w), y)
            jax.block_until_ready(y)
            traces.append(OpTrace(name, "execute", "big",
                                  ts - t0, time.perf_counter() - t0))
        return RunResult(output=y, total_s=time.perf_counter() - t0,
                         traces=traces)
