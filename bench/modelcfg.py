"""A configuration file (``bench/configs/<name>.json``) read into the model
dict the benchmark passes around, and the model family that knows its
shapes.

The file holds the published ``config.json`` keys as run, and the
harness's own: ``family``, the name of ``bench/families/<family>.py``,
which reads the published keys; ``reference``, the name of
``bench/refs/<reference>.py``; and ``limits``, the numbers that decide
``correct``. The model dict is the family's sizes with ``name``,
``family``, ``dtype`` (the published ``torch_dtype``), ``reference`` and
``limits``.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Dict

ROOT = Path(__file__).resolve().parent.parent


def family(m: Dict[str, Any], root: Path = ROOT):
    """The family module of model ``m``, from ``root``'s
    ``bench/families``; a family without a file is an error."""
    path = Path(root) / "bench" / "families" / f"{m['family']}.py"
    if not path.is_file():
        raise FileNotFoundError(f"model {m['name']!r} names family "
                                f"{m['family']!r}, which has no file {path}")
    spec = importlib.util.spec_from_file_location(f"family_{path.stem}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model(path: Path, name: str, root: Path = ROOT) -> Dict[str, Any]:
    c = json.loads(Path(path).read_text())
    m = {"name": name, "family": c["family"]}
    m.update(family(m, root).model(c))
    m.update(dtype=c["torch_dtype"], reference=c["reference"],
             limits=c.get("limits", {}))
    return m
