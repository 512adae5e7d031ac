"""Cold-start LLM serving through the persistent executor: a ColdServer
admits the model, the cold task graph streams weights from disk while the
prefill executes layer-by-layer (execute-as-you-load), the first token is
sampled from the streamed prefill, and decode continues on a BatchedServer
whose per-layer decode params were packed in the background — the first
token is out before the last layer's decode-path prep completes.

Run: PYTHONPATH=src python examples/serve_cold_llm.py
"""
import tempfile

import jax
import numpy as np

from repro.configs import get_config
from repro.core.compile_cache import setup_compile_cache
from repro.core.llm_graph import build_llm_graph
from repro.executor.llm_bridge import cold_start_llm
from repro.executor.server import ColdServer
from repro.models import transformer as T


def main():
    setup_compile_cache()
    # ~65M-param smollm-family model (f32 master checkpoint ≈ 260 MB on disk)
    cfg = get_config("smollm-360m").reduced(
        num_layers=8, d_model=512, d_ff=1536, num_heads=8, num_kv_heads=4,
        head_dim=64, vocab_size=16_384)
    print(f"model: {cfg.name} ≈{cfg.param_count()/1e6:.0f}M params")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    graph, toks = build_llm_graph(cfg, params)

    with tempfile.TemporaryDirectory() as root:
        server = ColdServer(root, n_little=3, max_concurrent_preps=2)
        eng = server.add_model("smollm", graph)
        stats = server.decide("smollm", toks)
        kinds = {}
        for name, (kern, cached) in stats["choices"].items():
            kinds[(kern, cached)] = kinds.get((kern, cached), 0) + 1
        print(f"offline plan: {stats['plan_generation_s']:.1f}s; "
              f"kernel choices {kinds}")
        print(f"storage: raw {stats['model_bytes']/1e6:.0f} MB + "
              f"bf16 cache {stats['cache_bytes']/1e6:.0f} MB")

        res = cold_start_llm(eng, cfg, toks[0], max_new_tokens=8,
                             n_little=3, server=server, model_name="smollm")
        print(f"first token at {res.first_token_s*1e3:.0f} ms "
              f"({res.overlapped_layers} prep ops still in flight when the "
              f"exec chain started; {res.overlapped_packs} decode packs "
              f"overlapped it)")
        print(f"last weight prep {res.last_weight_prep_s*1e3:.0f} ms | "
              f"last layer decode prep {res.decode_prep_s*1e3:.0f} ms | "
              f"decode ready {res.decode_ready_s*1e3:.0f} ms")
        print(f"tokens: {res.tokens}")

        cold = res.run                            # pipelined weight streaming
        seq = eng.run_cold(toks, mode="sequential")
        warm = eng.run_warm(toks)
        # first-prefill latency = end of the exec chain (res.first_token_s);
        # cold.total_s would also include the background decode-path packs
        print(f"cold first-prefill latency: nnv12 {res.first_token_s*1e3:.0f} ms "
              f"| sequential {seq.total_s*1e3:.0f} ms "
              f"| warm {warm*1e3:.0f} ms")
        print(f"  breakdown: "
              f"{ {k: round(v*1e3) for k, v in cold.stage_seconds().items()} }")
        agree = float(np.abs(np.asarray(cold.output)
                             - np.asarray(seq.output)).max())
        print(f"  logits agree vs baseline: {agree:.2e}")
        sim = eng.plan.est_makespan
        print(f"  sim-mode (big.LITTLE) est makespan: {sim*1e3:.0f} ms")


if __name__ == "__main__":
    main()
