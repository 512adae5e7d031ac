"""Unit tests of the benchmark's yardstick: operation counts, seeded
weights, spans, the store's filesystem, peaks, the client's clock and the
metric readers."""
import hashlib
import importlib.util
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import flops  # noqa: E402
import modelcfg  # noqa: E402
import filesystem  # noqa: E402
import spans  # noqa: E402

SMOLLM = modelcfg.model(BENCH / "configs" / "smollm-360m.json", "smollm")
DENSE = modelcfg.family(SMOLLM)
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_smollm_config_is_the_published_model():
    assert SMOLLM == {
        "name": "smollm", "family": "dense", "layers": 32, "d_model": 960,
        "d_ff": 2560, "vocab": 49152, "heads": 15, "kv_heads": 5,
        "head_dim": 64,
        "tied": True, "rope_theta": 10000.0, "norm_eps": 1e-5,
        "dtype": "bfloat16", "reference": "dense_decoder",
        "limits": SMOLLM["limits"]}


# two tiny dense configurations, one with an untied output head, and the
# SHA-256 of the weights the harness drew for each from seed 2**31 + 77
# before the dense family had a module of its own
TINY_DENSE = {
    "tiny-nemo": ({"hidden_size": 160, "intermediate_size": 448,
                   "num_hidden_layers": 2, "num_attention_heads": 4,
                   "num_key_value_heads": 1, "head_dim": 32,
                   "vocab_size": 512, "tie_word_embeddings": False,
                   "rope_theta": 1e6},
                  "aa5b0085a746a477ba11f3c4ceb5a619"
                  "3f50c15d96b196add5df92e25ddb9c61"),
    "tiny-tied": ({"hidden_size": 96, "intermediate_size": 256,
                   "num_hidden_layers": 3, "num_attention_heads": 3,
                   "num_key_value_heads": 1, "vocab_size": 384,
                   "tie_word_embeddings": True, "rope_theta": 1e4},
                  "4886a6f2c578029b2d9892f23459e608"
                  "eef0e83b5c9de7431e25335cfd35ee42"),
}


@pytest.mark.parametrize("name", list(TINY_DENSE))
def test_dense_family_draws_the_same_weights(name, tmp_path):
    import jax

    keys, want = TINY_DENSE[name]
    path = tmp_path / f"{name}.json"
    path.write_text(json.dumps(dict(
        keys, family="dense", reference="dense_decoder", rms_norm_eps=1e-5,
        torch_dtype="bfloat16")))
    m = modelcfg.model(path, name)
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(DENSE.make(2**31 + 77, m))
    for where, leaf in leaves[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(where)} {a.dtype} {a.shape};"
                 .encode())
        h.update(a.tobytes())
    assert h.hexdigest() == want


def test_tblock_matches_hand_count():
    # per block: wq 960x960, wk and wv 960x320, wo 960x960, three MLP
    # matrices 960x2560 -> 921600 + 2*307200 + 921600 + 3*2457600
    weights = 9_830_400
    # 2 per multiply-add for 128 rows; causal attention: 15 heads x 64 dims
    # x (1 + 2 + ... + 128) = 8256 pairs, twice (scores, values)
    assert DENSE.tblock(SMOLLM, 128)["flops"] == \
        2 * 128 * weights + 2 * 2 * 15 * 64 * 8256 == 2_548_285_440
    # bf16 weights and the two norm gains, the (128, 960) bf16 in and out
    assert DENSE.tblock(SMOLLM, 128)["bytes"] == \
        2 * (weights + 2 * 960) + 2 * 2 * 128 * 960 == 20_156_160


def test_decode_step_matches_hand_count():
    per_layer = 2 * 9_830_400 + 2 * 2 * 15 * 64 * 11     # attends to 0..10
    head = 2 * 960 * 49152
    assert DENSE.decode_step(SMOLLM, 10)["flops"] == \
        32 * per_layer + head == 724_869_120
    # weights, norm gains and keys+values of 11 positions x 5 heads x 64;
    # the output projection's weights and gain, its f32 logits, one
    # embedding row
    kv = 2 * 2 * 11 * 5 * 64
    per_layer_b = 2 * (9_830_400 + 2 * 960) + kv
    head_b = 2 * (960 * 49152 + 960) + 4 * 49152
    assert DENSE.decode_step(SMOLLM, 10)["bytes"] == \
        32 * per_layer_b + head_b + 2 * 960 == 724_291_328


def test_least_time_takes_the_binding_bound():
    work = DENSE.tblock(SMOLLM, 128)
    assert flops.least_time(work, V5E) == pytest.approx(20_156_160 / 819e9)
    big = DENSE.tblock(SMOLLM, 4096)
    assert flops.least_time(big, V5E) == pytest.approx(big["flops"] / 197e12)


def test_spans_union_and_clip():
    assert spans.merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == \
        [(0, 2.5), (3, 4)]
    assert spans.covered([(0, 1), (0.5, 2), (5, 6)]) == 3
    assert spans.clip([(0, 2), (3, 5), (6, 7)], 1, 4) == [(1, 2), (3, 4)]


def test_store_filesystem_is_named(tmp_path):
    fs, mnt = filesystem.filesystem_of(tmp_path)
    assert str(tmp_path).startswith(mnt)
    assert filesystem.on_disk(tmp_path) == (fs not in filesystem.MEMORY_FS)
    assert filesystem.filesystem_of("/proc")[0] == "proc"


def test_first_token_is_timed_before_the_bridge_copies_its_weights():
    """The bridge picks the first token from the result's ``output``, then
    reads its ``traces``, then registers its packed weights with
    ``register_packed_state``: the clock stops at the second step."""
    import time

    from client import Client

    class Ticket:
        job = type("Job", (), {"t0": 0.0})

        def result(self):
            return type("Result", (), {"output": None, "traces": []})

    class Server:
        def cold_start(self, *a, graph_hook=None, **kw):
            return Ticket()

        def register_packed_state(self, *a):
            pass

    c = Client(Server())
    res = c.cold_start("m", None).result()
    res.output
    before = time.perf_counter()
    res.traces
    after = time.perf_counter()
    time.sleep(0.01)
    c.register_packed_state("m", {})
    assert c.result_back <= before <= c.first_token <= after


def test_staged_below_counts_narrower_types():
    import jax.numpy as jnp

    from client import Client

    c = Client(None)
    c.staged = {
        "a": {"w": jnp.zeros((4, 4), jnp.bfloat16),
              "g": jnp.zeros(4, jnp.float32)},
        "b": {"w:q8": jnp.zeros((4, 4), jnp.int8),
              "w:qscale": jnp.zeros(4, jnp.float32)},
        "c": {"w": jnp.zeros((2, 2), jnp.float8_e4m3fn)}}
    assert c.staged_below("bfloat16") == 16 + 4
    assert c.staged_below("float32") == 32 + 16 + 4


def test_unknown_device_kind_has_no_peak():
    import device

    assert device.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peak("TPU v9 imaginary")


def _request(issue, first, picks, decode_start, reads=(), stages=()):
    return {"issue": issue, "first": first, "tokens": [first] + picks,
            "end": picks[-1], "decode_start": decode_start,
            "spans": {"read": list(reads), "stage": list(stages)}}


def test_end_to_end_readers_on_a_known_record():
    rec = {"requests": [
        _request(0.0, 2.0, [5.0, 5.1, 5.2], 4.0,
                 reads=[(0, 1), (0.5, 1.5)], stages=[(1, 2)]),
        _request(10.0, 14.0, [16.0, 16.3], 15.0,
                 reads=[(10, 10.5)], stages=[(11, 12), (13, 14)])],
        "setup_s": 42.0}
    assert reader("ttft_s")(rec) == pytest.approx(3.0)
    assert reader("handoff_s")(rec) == pytest.approx(2.5)
    # (0.2 + 0.3) s over 2 + 1 tokens after the second
    assert reader("tpot_ms")(rec) == pytest.approx(1000 * 0.5 / 3)
    assert reader("setup_s")(rec) == 42.0
    assert reader("read_busy_s")(rec) == pytest.approx((1.5 + 0.5) / 2)
    assert reader("stage_busy_s")(rec) == pytest.approx((1 + 2) / 2)
    assert reader("replay_s")(rec) == pytest.approx((1.0 + 1.0) / 2)


def test_trace_readers_are_silent_without_their_kernel():
    rec = {"trace": {"busy_s": 0.5, "window_s": 2.0, "kernels": {}}}
    assert reader("tblock_roofline")(rec) is None
    assert reader("decode_roofline")(rec) is None
    assert reader("device_idle")(rec) == pytest.approx(75.0)
    rec["trace"]["kernels"]["tblock"] = {"least_s": 1.0, "device_s": 4.0}
    assert reader("tblock_roofline")(rec) == pytest.approx(25.0)
