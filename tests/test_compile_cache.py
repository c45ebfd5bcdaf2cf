"""Where the entry points keep jax's persistent compilation cache."""

import json
import os
import subprocess
import sys

import jax

from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import json, sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from repro.launch.compile_cache import use_compile_cache

hits = []
jax.monitoring.register_event_listener(
    lambda event, **_: hits.append(event)
    if event == "/jax/compilation_cache/cache_hits" else None)
where = use_compile_cache()
out = jax.jit(lambda x: jnp.tanh(x) @ x.T)(jnp.ones((64, 64)))
jax.block_until_ready(out)
print(json.dumps({"dir": where, "hits": len(hits)}))
"""


def test_env_cache_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_cache_dir_is_fixed_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.use_compile_cache()
        assert compile_cache.use_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert first == os.path.join(ROOT, ".jax_cache")


def test_second_run_hits_the_cache(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    runs = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _SCRIPT, os.path.join(ROOT, "src")],
            env=env, capture_output=True, text=True, timeout=300,
        )
        assert out.returncode == 0, out.stderr[-3000:]
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    assert [r["dir"] for r in runs] == [str(tmp_path)] * 2
    assert runs[0]["hits"] == 0
    assert runs[1]["hits"] > 0
