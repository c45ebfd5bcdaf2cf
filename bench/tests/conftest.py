"""Benchmark tests: ``pytest bench/tests``.  They run on the CPU, at
small sizes; the repository's own suite collects only ``tests/``."""

import json
import os
import pathlib
import sys

import pytest

# four CPU devices, so that the four-chip deployment forms its sub-meshes;
# set before anything imports jax
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               "--xla_force_host_platform_device_count=4")

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

SMALL_TRAFFIC = {"shapes": [{"batch": 2, "prompt": 16},
                            {"batch": 4, "prompt": 32}],
                 "gen": [4, 8], "batches": 3, "sample_rows": 4}


@pytest.fixture(params=["v5e1-qwen2.5-3b"])
def small_live(request, monkeypatch):
    """A deployment (the one-chip one unless a test names another through
    ``indirect``) with the program's small qwen2.5-3b and a configuration
    file to match, for the CPU."""
    import repro.configs as configs
    import repro.launch.serve as serve

    monkeypatch.setattr(configs, "get", configs.get_smoke)
    monkeypatch.setattr(serve, "get", configs.get_smoke)
    cfg = configs.get_smoke("qwen2.5-3b")
    config = json.loads(
        (ROOT / "bench" / "configs" / f"{request.param}.json").read_text())
    config["model"].update(
        hidden_size=cfg.d_model, intermediate_size=cfg.d_ff,
        num_hidden_layers=cfg.n_layers, num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads, vocab_size=cfg.vocab_size)
    config["weights"]["padded_vocab"] = cfg.padded_vocab()
    return config, dict(SMALL_TRAFFIC)
