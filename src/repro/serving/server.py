"""Batched serving loop: continuous batching over prefill + decode steps.

A small but real server: requests enter a queue; the engine admits up to
``max_batch`` concurrent sequences into fixed slots; each scheduler tick
decodes one token for every live slot (one ``decode_step`` for the whole
batch); finished sequences free their slots for queued requests. Prefill of
a new request is a full-sequence ``forward(collect_cache=True)`` whose KV is
packed into the slot.

Combined with the ColdEngine, a cold-started server overlaps model weight
loading with the first prefill (examples/serve_cold.py).

Each tick is a ``decode.step`` span and each token's pick a ``decode.pick``
span (``executor.graph.span``), recorded into the cold start whose thread
runs the server. A pick holds a ``decode.wait`` span: the host waiting for
the device's token id; the rest of the pick is host work.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.executor.graph import span
from repro.models import transformer as T


def sample_token(logits: jax.Array, key, *, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 1.0) -> jax.Array:
    """Sample one token id from (V,) logits. temperature == 0 -> greedy.
    top_k and nucleus (top_p) filters compose."""
    if temperature <= 0.0:
        return jnp.argmax(logits)
    logits = logits.astype(jnp.float32) / temperature
    if top_k and top_k < logits.shape[-1]:
        kth = jnp.sort(logits)[-top_k]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits)[::-1]
        probs = jax.nn.softmax(sorted_logits)
        cum = jnp.cumsum(probs)
        # smallest set with cumulative prob >= top_p
        cutoff_idx = jnp.argmax(cum >= top_p)
        cutoff = sorted_logits[cutoff_idx]
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits)


@dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0     # 0 = greedy
    top_k: int = 0
    top_p: float = 1.0
    out_tokens: List[int] = field(default_factory=list)
    submitted_s: float = 0.0
    first_token_s: Optional[float] = None
    done_s: Optional[float] = None
    # set to [] to keep the float32 logits row each output token came from
    out_logits: Optional[List[np.ndarray]] = None


class BatchedServer:
    def __init__(self, params, cfg: ArchConfig, *, max_batch: int = 4,
                 max_len: int = 512, budget=None):
        """``budget`` (a ``repro.executor.server.MemoryBudget``, duck-typed
        ``reserve``/``release``) charges this server's KV-cache allocation
        to the SAME accounted pool the ColdServer's staged-weight LRU draws
        from: allocating KV for decode may evict another model's resident
        weights instead of silently overcommitting device memory.
        ``close()`` releases the reservation."""
        assert cfg.input_mode == "tokens", "server demo expects token models"
        self.params = params
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.state = T.init_decode_state(cfg, max_batch, max_len)
        self.kv_bytes = sum(int(getattr(x, "nbytes", 0))
                            for x in jax.tree.leaves(self.state))
        self.budget = budget
        self._budget_tag = f"kv:{id(self)}"
        if budget is not None:
            budget.reserve(self._budget_tag, self.kv_bytes)
        self.pos = np.zeros(max_batch, np.int64)        # per-slot position
        self.slot_req: List[Optional[Request]] = [None] * max_batch
        self.queue: List[Request] = []
        # completed since the last drain; run_until_drained hands the list
        # to the caller (a long-running server must not accumulate every
        # request it ever served)
        self.finished: List[Request] = []
        def decode_step(p, s, b, pos):
            return T.decode_step(p, s, b, pos, cfg)

        # named, so the program reads ``jit_decode_step`` in a trace
        self._decode = jax.jit(decode_step)
        self._t0 = time.perf_counter()
        self._key = jax.random.PRNGKey(0)

    def _pick(self, req: Request, logits_row: jax.Array) -> int:
        """Dispatch the key split and the sampling, then wait for the token
        id (and the row, where the request keeps its logits) in a
        ``decode.wait`` span."""
        self._key, sub = jax.random.split(self._key)
        tok = sample_token(logits_row, sub, temperature=req.temperature,
                           top_k=req.top_k, top_p=req.top_p)
        with span("decode.wait"):
            if req.out_logits is not None:
                req.out_logits.append(np.asarray(logits_row, np.float32))
            return int(tok)

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        req.submitted_s = time.perf_counter() - self._t0
        self.queue.append(req)

    def admit(self):
        """Move queued requests into free slots, replaying each prompt into
        its KV slot and picking its first new token."""
        for slot in range(self.max_batch):
            if self.slot_req[slot] is None and self.queue:
                req = self.queue.pop(0)
                self._prefill_into(slot, req)

    def _prefill_into(self, slot: int, req: Request):
        """Feed the prompt token-by-token through decode_step for the slot.

        (Slot-granular prefill via the batched decode path: correct if not
        maximal-throughput; a bulk prefill + cache-pack is the optimized
        path exercised by the dry-run's prefill_step.)"""
        self.slot_req[slot] = req
        toks = req.prompt.astype(np.int32)
        for t, tok in enumerate(toks):
            batch_tok = np.zeros((self.max_batch, 1), np.int32)
            batch_tok[slot, 0] = tok
            logits, self.state = self._decode(
                self.params, self.state,
                {"tokens": jnp.asarray(batch_tok)}, jnp.int32(self.pos[slot]))
            self.pos[slot] += 1
        with span("decode.pick"):
            nxt = self._pick(req, logits[slot, 0])
        req.out_tokens.append(nxt)
        req.first_token_s = time.perf_counter() - self._t0

    def step(self) -> int:
        """One decode tick for all live slots. Returns #live slots."""
        self.admit()
        live = [s for s in range(self.max_batch) if self.slot_req[s] is not None]
        if not live:
            return 0
        batch_tok = np.zeros((self.max_batch, 1), np.int32)
        for s in live:
            batch_tok[s, 0] = self.slot_req[s].out_tokens[-1]
        # single shared position per decode_step: use max slot pos (slots
        # prefilled at different times decode with their own mask lengths
        # tracked in the cache ring; demo server keeps slots in lockstep)
        pos = int(max(self.pos[s] for s in live))
        # the tick's span is its dispatch; the device's work ends inside
        # the picks' ``decode.wait`` spans, whose host copies wait for it
        with span("decode.step"):
            logits, self.state = self._decode(
                self.params, self.state,
                {"tokens": jnp.asarray(batch_tok)}, jnp.int32(pos))
        for s in live:
            self.pos[s] = pos + 1
            req = self.slot_req[s]
            with span("decode.pick"):
                tok = self._pick(req, logits[s, 0])
            req.out_tokens.append(tok)
            if len(req.out_tokens) >= req.max_new_tokens:
                req.done_s = time.perf_counter() - self._t0
                self.finished.append(req)
                self.slot_req[s] = None
        return len(live)

    def close(self):
        """Release the KV-cache reservation back to the shared budget.
        Idempotent; the server itself remains usable (the accounting is
        advisory — correctness never depends on it)."""
        if self.budget is not None:
            self.budget.release(self._budget_tag)
            self.budget = None

    def run_until_drained(self, max_ticks: int = 10_000) -> List[Request]:
        """Tick until queue and slots are empty; returns every request
        finished since the last drain (in completion order) and clears the
        buffer — ownership passes to the caller."""
        for _ in range(max_ticks):
            if not self.queue and all(r is None for r in self.slot_req):
                break
            self.step()
        out, self.finished = self.finished, []
        return out
