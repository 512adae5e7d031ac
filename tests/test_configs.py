"""Config sanity: analytic parameter counts must land near the nominal
model sizes the architecture ids claim; reduced variants stay tiny."""
import pytest

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config

NOMINAL = {
    "zamba2-2.7b": 2.7e9,
    "granite-moe-3b-a800m": 3.0e9,
    "smollm-360m": 0.36e9,
    "mamba2-2.7b": 2.7e9,
    "qwen3-moe-30b-a3b": 30e9,
    "musicgen-medium": 1.5e9,   # medium ≈ 1.5B
    "mistral-nemo-12b": 12e9,
    "gemma2-27b": 27e9,
    "internvl2-76b": 76e9,      # incl. vision tower; LLM part ≈ 70B
    "qwen3-32b": 32e9,
    "mellum2-12b-a2.5b": 12e9,
}

ACTIVE = {
    "granite-moe-3b-a800m": 0.8e9,
    "qwen3-moe-30b-a3b": 3e9,
    "mellum2-12b-a2.5b": 2.5e9,
}


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_param_count_near_nominal(arch):
    cfg = get_config(arch)
    n = cfg.param_count()
    lo, hi = NOMINAL[arch] * 0.6, NOMINAL[arch] * 1.45
    assert lo <= n <= hi, (arch, f"{n/1e9:.2f}B vs nominal {NOMINAL[arch]/1e9}B")


@pytest.mark.parametrize("arch", list(ACTIVE))
def test_moe_active_params(arch):
    cfg = get_config(arch)
    a = cfg.active_param_count()
    assert ACTIVE[arch] * 0.5 <= a <= ACTIVE[arch] * 1.6, f"{a/1e9:.2f}B"
    assert a < cfg.param_count() * 0.5  # sparsity is real


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_reduced_is_small(arch):
    r = get_config(arch).reduced()
    assert r.num_layers <= 2
    assert r.d_model <= 512
    assert (r.num_experts or 0) <= 4
    assert r.param_count() < 5e6 + r.vocab_size * r.d_model * 2


def test_shapes_registry():
    assert set(INPUT_SHAPES) == {"train_4k", "prefill_32k", "decode_32k",
                                 "long_500k"}
    assert INPUT_SHAPES["long_500k"].seq_len == 524_288
    assert INPUT_SHAPES["train_4k"].global_batch == 256
