"""Host→device weight staging — the pipeline's 'stage' op.

``stage(w)`` is how every caller stages a layer's weights for execution.
It picks its route once per process, from the backend JAX reports:

* **On an accelerator, the DMA queue** (``_DmaQueue``): one thread copies
  each tensor into a pinned bounce buffer, ``device_put``\\ s it from there
  and blocks until it lands. Stages are serialized, so staging transfers
  never contend with the exec chain's own copies; a failure comes back to
  the caller as a retryable ``StageFault``.
* **On CPU, ``stage_weights`` inline.** The CPU backend zero-copy
  *aliases* suitably aligned host buffers instead of copying them, so a
  recycled bounce buffer would be overwritten under a live alias. A
  read-only mmap view from a weight bundle (64-byte-aligned by
  construction) staged that way would keep pointing at file-backed pages,
  leaving its disk I/O to fault in lazily inside the execute op — exactly
  the host-side work staging exists to move off the critical exec chain.
  ``stage_weights`` therefore materializes read-only (file-backed) views
  into anonymous memory first; heap arrays produced by kernel transforms
  pass straight through.

The profiler times ``stage_weights`` for its measured ``stage_s``; on an
accelerator that is not the route ``stage`` takes.

``COUNTERS`` are the process's staging counters, which the cold path's
``stage`` ops feed: ``expert_stage_bytes``, the bytes of held-expert
weights (a unit's weights named ``EXPERT_PREFIX...``) staged, and
``expert_stage_jobs``, the cold starts whose graph holds such weights.
"""
from __future__ import annotations

import collections
import queue
import threading
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from repro.faults import StageFault
from repro.ioengine import PinnedBufferPool

#: the weight names of a sparse-expert unit's held experts start with this
EXPERT_PREFIX = "expert_"
COUNTERS: collections.Counter = collections.Counter()


def expert_bytes(w: Dict[str, Any]) -> int:
    """Bytes of the held-expert weights among ``w``."""
    return sum(int(v.nbytes) for k, v in w.items()
               if k.startswith(EXPERT_PREFIX))


def stage_weights(w: Dict[str, Any]) -> Dict[str, Any]:
    staged = {}
    for k, v in w.items():
        if isinstance(v, np.ndarray) and not v.flags.writeable:
            v = np.array(v)  # fault file-backed pages into anonymous memory
        staged[k] = jax.device_put(v)
    if staged:
        jax.block_until_ready(staged)
    return staged


class _DmaQueue:
    """The accelerator route: a dedicated thread stages one layer dict at a
    time, each tensor through a pinned bounce buffer."""

    def __init__(self, pool: Optional[PinnedBufferPool] = None):
        self.pool = pool or PinnedBufferPool()
        self._q: "queue.Queue" = queue.Queue()
        self._thread = threading.Thread(
            target=self._loop, name="repro-stage-dma", daemon=True)
        self._thread.start()

    def _loop(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            w, out, done = item
            try:
                staged = {}
                for k, v in w.items():
                    arr = np.asarray(v)
                    buf = self.pool.acquire(arr.nbytes)
                    try:
                        bounce = buf.arr[:arr.nbytes].view(arr.dtype).reshape(
                            arr.shape)
                        np.copyto(bounce, arr)
                        # device_put copies across the bus on accelerators;
                        # the bounce buffer is free to recycle right after
                        staged[k] = jax.device_put(bounce)
                        jax.block_until_ready(staged[k])
                    finally:
                        buf.release()
                out["staged"] = staged
            except BaseException as e:
                out["error"] = e
            finally:
                done.set()

    def __call__(self, w: Dict[str, Any]) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        done = threading.Event()
        self._q.put((w, out, done))
        done.wait()
        if "error" in out:
            err = out["error"]
            raise StageFault(f"dma stage failed: {err}") from err
        return out["staged"]

    def close(self) -> None:
        self._q.put(None)
        self._thread.join(timeout=5.0)
        self.pool.close()


_route: Optional[Callable[[Dict[str, Any]], Dict[str, Any]]] = None
_route_lock = threading.Lock()


def _pick_route() -> Callable[[Dict[str, Any]], Dict[str, Any]]:
    global _route
    with _route_lock:
        if _route is None:
            # no try/except: a backend that fails to initialise must surface
            # here, not quietly select the CPU route
            _route = (stage_weights if jax.default_backend() == "cpu"
                      else _DmaQueue())
        return _route


def route_name() -> str:
    """``"dma"`` or ``"inline"``: the route ``stage`` takes here."""
    return "inline" if _pick_route() is stage_weights else "dma"


def stage(w: Dict[str, Any]) -> Dict[str, Any]:
    """Stage one layer's weights for execution: device-resident arrays,
    landed before this returns."""
    if not w:
        return {}
    return (_route or _pick_route())(w)
