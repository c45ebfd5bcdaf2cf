"""H4 regression: KV-length-sharded decode lowers and runs on a mesh whose
model axis does not divide the kv-head count (flash-decoding layout)."""

import subprocess
import sys

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from repro.configs import SMOKES
from repro.launch.mesh import make_mesh, mesh_shape_dict
from repro.models.config import ShapeConfig
from repro.models.model import build_model
from repro.parallel.sharding import make_rules
from repro.parallel.steps import make_decode_step, make_prefill_step

cfg = SMOKES["qwen2.5-3b"]         # kv=2: cannot shard over a 4-way axis
mesh = make_mesh((2, 4), ("data", "model"))
rules = make_rules(cfg, mesh_shape_dict(mesh), fsdp=False, batch_size=2)
assert rules.rules["kv_heads"] == ()
assert rules.rules["kv_len"] == ("model",)

model = build_model(cfg)
shape = ShapeConfig("d", 32, 2, "decode")
pre = make_prefill_step(model, rules, mesh, ShapeConfig("p", 32, 2, "prefill"))
dec = make_decode_step(model, rules, mesh, shape)
with mesh:
    params = model.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 36), 0, cfg.vocab_size)
    pfn = jax.jit(pre.fn, in_shardings=pre.in_shardings,
                  out_shardings=pre.out_shardings)
    dfn = jax.jit(dec.fn, in_shardings=dec.in_shardings,
                  out_shardings=dec.out_shardings,
                  donate_argnums=dec.donate_argnums)
    lg, cache = pfn(params, {"tokens": toks[:, :32]})
    for i in range(32, 36):
        lg, cache = dfn(params, cache, toks[:, i:i + 1])

# ground truth on the same devices without the sharded cache
ref_model = build_model(cfg)
lg_ref, _ = ref_model.prefill(params, {"tokens": toks})
import numpy as np
err = float(jnp.max(jnp.abs(lg.astype(jnp.float32) - lg_ref.astype(jnp.float32))))
assert err < 0.35, err   # bf16 path divergence only
print("KV_SHARD_OK", err)
"""


def test_kv_length_sharded_decode_runs_and_matches():
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT, "src"],
        capture_output=True, text=True, timeout=900, cwd=".",
    )
    assert "KV_SHARD_OK" in out.stdout, out.stdout[-1500:] + out.stderr[-3000:]
