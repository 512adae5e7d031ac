"""The chips a run uses, as JAX reports them, and their published peaks."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict, List

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def require(platform: str, chips: int) -> List[Any]:
    """The first ``chips`` devices; raises unless JAX's default backend is
    ``platform`` with at least that many. There is no fallback."""
    import jax

    devs = jax.devices()
    if devs[0].platform != platform or len(devs) < chips:
        raise RuntimeError(
            f"this cell needs {chips} {platform} chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s) "
            f"({devs[0].device_kind})")
    return devs[:chips]


def peak(kind: str, path: Path = PEAKS) -> Dict[str, float]:
    """The published peaks of ``kind``; a kind not in the table is an
    error, never a default."""
    table = json.loads(Path(path).read_text())
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"{path}")
    return table[kind]


def describe(devs: List[Any]) -> Dict[str, Any]:
    """Platform, kind, count and the fullest chip's peak memory."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}
