"""The client's view of one cold LLM request: when each token id reached
the host, read on the host clock around the calls into the program, and
what the request staged on the device.

``cold_start_llm`` answers with all of its tokens at once, so the
benchmark stands beside it where the tokens appear:

* ``Client`` is handed to ``cold_start_llm`` in the ``ColdServer``'s
  place and passes every call through. The bridge takes the streamed
  prefill's logits from the job's result (``output``) to the host, picks
  the first token there, and only then reads the result's ``traces``:
  that first read is the first token's time (or, should the bridge never
  read them, the time the result came back). What the bridge does next,
  registering the packed decode weights, which stay on the device, for
  warm-state transfer (``register_packed_state``), belongs to the
  hand-off. Decode starts when the decode server reserves its KV cache.
  The graph hook is wrapped to keep the job's staged weights, so that
  their types can be read once the request has ended.
* ``token_clock`` wraps ``BatchedServer._pick``, which returns each later
  token id as a host integer, and notes the time it returns.

With a profiler running, the two phases are also marked in its trace as
``bench.prefill`` and ``bench.decode``, so that device time can be told
apart by phase.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List, Optional


class _Mark:
    """A named span in the profiler's trace, opened and closed on the
    client's thread by two separate calls."""

    def __init__(self, name: str, on: bool):
        self.ann = None
        if on:
            import jax

            self.ann = jax.profiler.TraceAnnotation(name)
            self.ann.__enter__()

    def close(self) -> None:
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
            self.ann = None


class _Budget:
    def __init__(self, client: "Client", budget: Any):
        self._c, self._b = client, budget

    def reserve(self, tag: str, nbytes: int):
        if tag.startswith("kv:") and self._c.decode_start is None:
            self._c.decode_start = time.perf_counter()
            self._c.mark("bench.decode")
        return self._b.reserve(tag, nbytes)

    def __getattr__(self, name: str):
        return getattr(self._b, name)


class _Ticket:
    def __init__(self, client: "Client", ticket: Any):
        self._c, self._t = client, ticket
        self.job = ticket.job

    def result(self, *a, **kw):
        res = self._t.result(*a, **kw)
        self._c.result_back = time.perf_counter()
        self._c.end_mark()
        return _Result(self._c, res)

    def __getattr__(self, name: str):
        return getattr(self._t, name)


class _Result:
    def __init__(self, client: "Client", res: Any):
        self._c, self._r = client, res

    @property
    def traces(self):
        if self._c.token_picked is None:
            self._c.token_picked = time.perf_counter()
        return self._r.traces

    def __getattr__(self, name: str):
        return getattr(self._r, name)


class Client:
    """Passes every call through to ``server`` and notes the times above."""

    def __init__(self, server: Any, *, annotate: bool = False):
        self._s = server
        self._annotate = annotate
        self._mark: Optional[_Mark] = None
        self.job_t0: Optional[float] = None
        self.result_back: Optional[float] = None
        self.token_picked: Optional[float] = None
        self.decode_start: Optional[float] = None
        self.staged: Dict[str, Dict[str, Any]] = {}

    def mark(self, name: str) -> None:
        self.end_mark()
        self._mark = _Mark(name, self._annotate)

    def end_mark(self) -> None:
        if self._mark is not None:
            self._mark.close()
            self._mark = None

    def cold_start(self, *a, graph_hook=None, **kw):
        def hook(graph, weights, lock):
            self.staged = weights
            if graph_hook is not None:
                graph_hook(graph, weights, lock)

        ticket = self._s.cold_start(*a, graph_hook=hook, **kw)
        self.job_t0 = ticket.job.t0
        return _Ticket(self, ticket)

    @property
    def budget(self):
        return _Budget(self, self._s.budget)

    @property
    def first_token(self) -> Optional[float]:
        return self.token_picked if self.token_picked is not None \
            else self.result_back

    def staged_below(self, dtype: str) -> int:
        """Bytes the request staged in a narrower type than ``dtype``:
        integers, or floats of fewer bits."""
        import jax.numpy as jnp

        width = jnp.dtype(dtype).itemsize
        return sum(int(v.nbytes) for w in self.staged.values()
                   for v in w.values()
                   if not jnp.issubdtype(v.dtype, jnp.floating)
                   or jnp.dtype(v.dtype).itemsize < width)

    def __getattr__(self, name: str):
        return getattr(self._s, name)


@contextlib.contextmanager
def token_clock(picks: List[float]):
    """While open, append the host time at which each decoded token id is
    returned by ``BatchedServer._pick``."""
    from repro.serving.server import BatchedServer

    orig = BatchedServer._pick

    def pick(self, req, row):
        tok = orig(self, req, row)
        picks.append(time.perf_counter())
        return tok

    BatchedServer._pick = pick
    try:
        yield picks
    finally:
        BatchedServer._pick = orig
