"""Where compile caches live: under ``$JAX_COMPILATION_CACHE_DIR`` when it is
set, else at the checkout's fixed ``.jax_cache/`` — never under a model
store, a temporary name, a pid or a time."""
import json
import os
import subprocess
import sys
from pathlib import Path

from repro.core import compile_cache as cc

REPO = Path(__file__).resolve().parents[1]

_COLD_START = """
import json, sys
import jax
from repro.core.compile_cache import setup_compile_cache
from repro.core.engine import ColdEngine
from repro.models.cnn import build_cnn

root = setup_compile_cache()
layers, x = build_cnn("mobilenet", image=16, width=0.25)
eng = ColdEngine(layers, sys.argv[1])
eng.decide(x, n_little=2)
eng.run_cold(x)
print(json.dumps({"root": str(root),
                  "jax_dir": jax.config.jax_compilation_cache_dir,
                  "stats": eng.compile_cache.stats}))
"""


def test_cold_start_caches_land_under_env_dir(tmp_path):
    cache, store = tmp_path / "cache", tmp_path / "store"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               # persist every compile, however quick, so JAX's own cache
               # shows up in the placement check too
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=str(REPO / "src"))
    out = subprocess.run([sys.executable, "-c", _COLD_START, str(store)],
                         env=env, capture_output=True, text=True,
                         timeout=300, check=True)
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["root"] == str(cache) and got["jax_dir"] == str(cache)
    assert got["stats"]["misses"] + got["stats"]["disk_hits"] > 0
    execs = list((cache / "executables").glob("*.xla"))
    assert execs, "the executable cache wrote nothing under the env dir"
    jax_entries = [p for p in cache.iterdir() if p.is_file()]
    assert jax_entries, "JAX's persistent cache wrote nothing under it"
    # nothing cache-like under the model store
    assert not list(store.rglob("*.xla"))
    assert not (store / "xla_cache").exists()


def test_cache_root_is_env_dir_or_fixed_checkout_path(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cc.cache_root() == tmp_path
    assert cc.executable_cache_dir() == tmp_path / "executables"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert cc.cache_root() == REPO / ".jax_cache"
    assert cc.executable_cache_dir().parent == REPO / ".jax_cache"
