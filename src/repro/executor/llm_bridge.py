"""Cold-LLM bridge: engine-streamed prefill → BatchedServer decode.

A cold LLM start becomes a first-token-optimal pipeline:

  1. the cold task graph streams block weights from disk and *executes the
     prefill as layers stage* (execute-as-you-load): early blocks compute
     the prompt while later blocks are still being read/transformed — the
     first token is sampled from the streamed prefill's logits;
  2. per-layer ``pack`` tasks — appended to the same task graph — convert
     each block's staged weights into the ``BatchedServer``'s decode param
     layout (deployed dtype, T-format pytree). A layer's pack depends on
     its *execute*, never just its stage: decode-path packing must not
     compete with the critical exec chain for the first token, so the last
     layer's decode prep always completes after the first token is out;
  3. once every pack landed, the stacked decode params feed a
     ``BatchedServer`` that replays the prompt (+ the already-emitted first
     token) into a KV slot and continues decoding.

The result records the first-token timestamp against the job clock next to
the prep/pack trace ends, and every span of the cold start on that clock
(``ColdLLMResult.spans``): the task graph's, the compiles, the bridge's
own steps (``first_token``, ``handoff.copy``, ``handoff.stack``,
``handoff.replay``), the decode server's ticks and picks, and the garbage
collections that ran meanwhile.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import quant
from repro.configs.base import ArchConfig
from repro.core.engine import ColdEngine
from repro.core.pipeline import RunResult
from repro.executor.graph import (
    OpTrace, PREP_KINDS, collections_between, serving, span,
)
from repro.serving.server import BatchedServer, Request


@dataclass
class ColdLLMResult:
    tokens: List[int]                 # first token + decoded continuation
    first_token: int
    first_token_s: float              # job clock: streamed-prefill logits out
    last_weight_prep_s: float         # last read/transform/stage trace end
    decode_prep_s: float              # last 'pack' end (per-layer decode prep)
    decode_ready_s: float             # params stacked + KV slot prefilled
    overlapped_layers: int            # preps still unfinished at first execute
    overlapped_packs: int             # packs started before the exec chain ended
    run: RunResult = field(repr=False, default=None)
    # (len(tokens), V) float32: row t is the logits tokens[t] was picked
    # from (row 0 from the streamed prefill, the rest from decode through
    # the KV cache); only with ``keep_logits=True``
    logits: Optional[np.ndarray] = field(repr=False, default=None)

    @property
    def spans(self) -> List[OpTrace]:
        """Every span of this cold start, on the job clock and with the
        job's ``request``: the run's traces, which the bridge extends with
        its own spans, the decode server's and the collections inside the
        call."""
        return self.run.traces


def _expand_quantized(w: Dict[str, Any],
                      logical_shapes: Dict[str, tuple]) -> Dict[str, Any]:
    """Quantized cache entries stage as companion groups (``base:q8`` /
    ``base:q4`` + ``base:qscale``). The BatchedServer decode path wants the
    logical tensors, so packing dequantizes them here; the quantized form
    only serves the cold read + streamed prefill. ``logical_shapes`` (from
    the layer spec) recovers an odd K that int4 packing rounded up."""
    if not quant.is_quantized(w):
        return w
    groups, rest = quant.split_groups(w)
    for base in groups:
        rest[base] = quant.dequantize_weight(w, base,
                                             logical_shapes.get(base))
    return rest


def _pack_params(cfg: ArchConfig, packed: Dict[str, Dict[str, Any]]):
    """Stack per-layer packed weights into the T-format decode pytree."""
    blocks = []
    for i in range(cfg.num_layers):
        w = packed[f"block{i:03d}"]
        attn = {k: w[k] for k in ("wq", "wk", "wv", "wo")}
        if cfg.qk_norm:
            attn["q_norm"], attn["k_norm"] = w["q_norm"], w["k_norm"]
        blocks.append({"ln1": w["ln1"], "ln2": w["ln2"], "attn": attn,
                       "mlp": {k: w[k]
                               for k in ("w_gate", "w_up", "w_down")}})
    params: Dict[str, Any] = {
        "embed": packed["embed"]["embed"],
        "final_norm": packed["lm_head"]["final_norm"],
        "blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *blocks),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = packed["lm_head"]["w"]
    return params


def cold_start_llm(
    engine: ColdEngine,
    cfg: ArchConfig,
    prompt: np.ndarray,               # (S,) int32 token ids
    *,
    max_new_tokens: int = 8,
    n_little: int = 3,
    server: Optional[Any] = None,     # ColdServer for admission (optional)
    model_name: Optional[str] = None,
    keep_logits: bool = False,
) -> ColdLLMResult:
    """Cold-start a ``build_llm_graph`` engine and serve ``max_new_tokens``
    greedily; see the module docstring for the pipeline."""
    assert engine.plan is not None, "decide() first"
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    x = prompt[None, :]
    dtype = jnp.dtype(cfg.dtype)
    packed: Dict[str, Dict[str, Any]] = {}
    shapes = {l.spec.name: l.spec.weight_shapes for l in engine.layers}

    def hook(graph, weights, lock):
        # decode-path packing: one task per weighted layer, scheduled after
        # the layer's execute so it never delays the exec chain; 'any'
        # affinity — idle littles pack early blocks while later blocks
        # still prep/execute
        for t in [t for t in graph.tasks if t.kind == "execute"]:
            name = t.layer

            def fn(name=name):
                with lock:
                    w = weights.get(name) or {}
                w = _expand_quantized(w, shapes.get(name) or {})
                packed[name] = {k: jnp.asarray(v, dtype)
                                for k, v in w.items()}

            if graph.task(name, "stage") is not None:   # weighted layers only
                graph.add(name, "pack", affinity="any", deps=(t.tid,), fn=fn)

    called = time.perf_counter()
    if server is not None:
        ticket = server.cold_start(model_name, x, n_little=n_little,
                                   graph_hook=hook)
        pjob, res = ticket.job, ticket.result()
    else:
        pjob = engine.submit_cold(x, n_little=n_little, graph_hook=hook)
        res = pjob.result()
    job = pjob.job                      # the pool's: seq, clock, traces

    with span("first_token", job=job):
        logits = np.asarray(res.output)              # (1, S, V) float32
        first_token = int(np.argmax(logits[0, -1]))
    exec_traces = [t for t in res.traces if t.kind == "execute"]
    first_token_s = max(t.end for t in exec_traces)
    first_exec_start = min(t.start for t in exec_traces)
    prep_traces = [t for t in res.traces if t.kind in PREP_KINDS]
    last_weight_prep_s = max(t.end for t in prep_traces)
    pack_traces = [t for t in res.traces if t.kind == "pack"]
    decode_prep_s = max(t.end for t in pack_traces)
    overlapped = sum(1 for t in prep_traces if t.end > first_exec_start)
    overlapped_packs = sum(1 for t in pack_traces if t.start < first_token_s)

    # packed decode params are now "present" on this worker: register them
    # with the ColdServer so sibling workers' warm-state fetches can ride
    # them over the transfer stream (the ``__packed__`` pseudo-layer),
    # flattened to "layer/key" so they cross the wire as plain arrays
    if server is not None and model_name is not None:
        with span("handoff.copy", job=job):
            flat = {f"{lname}/{k}": np.asarray(v)
                    for lname, kv in packed.items() for k, v in kv.items()}
            server.register_packed_state(model_name, flat)

    # decode continuation: stack params, replay prompt + token 1 into a KV
    # slot, decode the rest greedily; the KV allocation draws from the
    # ColdServer's shared memory budget when one is serving this request
    with span("handoff.stack", job=job):
        params = _pack_params(cfg, packed)
        srv = BatchedServer(params, cfg, max_batch=1,
                            max_len=int(prompt.size + max_new_tokens + 2),
                            budget=(server.budget if server is not None
                                    else None))
    tokens = [first_token]
    rows = [logits[0, -1]]
    if max_new_tokens > 1:
        req = Request(rid=0,
                      prompt=np.concatenate([prompt, [first_token]]),
                      max_new_tokens=max_new_tokens - 1,
                      out_logits=[] if keep_logits else None)
        with span("handoff.replay", job=job):
            srv.submit(req)
            srv.admit()  # replays the prompt into the KV slot, picks token 2
        # decode-ready = params stacked + KV slot prefilled (NOT the full
        # decode drain — that scales with max_new_tokens)
        decode_ready_s = time.perf_counter() - job.t0
        with serving(job):          # its ticks' and picks' spans
            srv.run_until_drained()
        assert req.done_s is not None, "decode did not drain"
        tokens += [int(tk) for tk in req.out_tokens]
        rows += req.out_logits or []
    else:
        decode_ready_s = time.perf_counter() - job.t0
    srv.close()     # return the KV reservation to the shared budget
    for c in collections_between(called, time.perf_counter()):
        job.traces.append(dataclasses.replace(
            c, start=c.start - job.t0, end=c.end - job.t0, request=job.seq))

    return ColdLLMResult(
        tokens=tokens, first_token=first_token,
        first_token_s=first_token_s,
        last_weight_prep_s=last_weight_prep_s,
        decode_prep_s=decode_prep_s, decode_ready_s=decode_ready_s,
        overlapped_layers=overlapped, overlapped_packs=overlapped_packs,
        run=res, logits=np.stack(rows) if keep_logits else None,
    )
