"""A configuration file (``bench/configs/<name>.json``, the published
``config.json`` keys as run) read into the sizes the benchmark uses."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict


def model(path: Path, name: str) -> Dict[str, Any]:
    c = json.loads(Path(path).read_text())
    heads = c["num_attention_heads"]
    return {
        "name": name,
        "layers": c["num_hidden_layers"],
        "d_model": c["hidden_size"],
        "d_ff": c["intermediate_size"],
        "vocab": c["vocab_size"],
        "heads": heads,
        "kv_heads": c["num_key_value_heads"],
        "head_dim": c.get("head_dim") or c["hidden_size"] // heads,
        "tied": c["tie_word_embeddings"],
        "rope_theta": float(c["rope_theta"]),
        "norm_eps": float(c["rms_norm_eps"]),
        "dtype": c["torch_dtype"],
        "reference": c["reference"],
        "limits": c.get("limits", {}),
    }


def arch_config(m: Dict[str, Any]):
    """The program's ``ArchConfig`` for ``m``: a dense decoder."""
    from repro.configs.base import ArchConfig

    return ArchConfig(
        name=m["name"], family="dense", num_layers=m["layers"],
        d_model=m["d_model"], d_ff=m["d_ff"], vocab_size=m["vocab"],
        num_heads=m["heads"], num_kv_heads=m["kv_heads"],
        head_dim=m["head_dim"], rope_theta=m["rope_theta"],
        norm_eps=m["norm_eps"], tie_embeddings=m["tied"], dtype=m["dtype"])
