"""Host→device weight staging — the pipeline's 'stage' op.

One subtlety makes this more than a loop of ``jax.device_put``: the CPU
backend zero-copy *aliases* suitably aligned host buffers instead of
copying them. A read-only mmap view from a weight bundle (64-byte-aligned
by construction) staged that way would keep pointing at file-backed pages,
leaving its disk I/O to fault in lazily inside the execute op — exactly
the host-side work staging exists to move off the critical exec chain.

``stage_weights`` therefore materializes read-only (file-backed) views
into anonymous memory first: the stage op pays the page-in and transfer
cost, and execute runs against device-resident buffers that can never
touch the disk. Heap arrays produced by kernel transforms pass straight
through. The profiler uses the same helper, so measured ``stage_s`` is
the cost the runtime actually pays.

``COUNTERS`` are the process's staging counters, which the cold path's
``stage`` ops feed: ``expert_stage_bytes``, the bytes of held-expert
weights (a unit's weights named ``EXPERT_PREFIX...``) staged, and
``expert_stage_jobs``, the cold starts whose graph holds such weights.
"""
from __future__ import annotations

import collections
from typing import Any, Dict

import jax
import numpy as np

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
