"""Gradient compression: quantisation, error feedback, int8 ring."""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.parallel.compression import (
    dequantize_int8,
    ef_compress,
    ef_init,
    quantize_int8,
)


def test_quantize_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.key(0), (256,), jnp.float32) * 3.0
    q, s = quantize_int8(x)
    err = jnp.max(jnp.abs(dequantize_int8(q, s) - x))
    assert float(err) <= float(s) * 0.5 + 1e-6


def test_error_feedback_is_unbiased_over_time():
    """Sum of compressed gradients converges to the sum of raw gradients."""
    rng = np.random.default_rng(0)
    grads = [jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
             for _ in range(50)]
    err = ef_init({"g": grads[0]})
    total_raw = jnp.zeros((64,))
    total_comp = jnp.zeros((64,))
    for g in grads:
        comp, err = ef_compress({"g": g}, err)
        total_raw += g
        total_comp += comp["g"]
    # residual error stays bounded by one quantisation step, it never grows
    resid = jnp.max(jnp.abs(total_raw - total_comp))
    scales = [quantize_int8(g)[1] for g in grads]
    assert float(resid) < 3 * float(max(scales))


_RING_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
import numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.parallel.compression import ring_allreduce_int8

mesh = make_mesh((8,), ("pod",))
x = jax.random.normal(jax.random.key(0), (8, 64), jnp.float32)

@partial(jax.shard_map, mesh=mesh, in_specs=P("pod", None),
         out_specs=P("pod", None))
def ring(v):
    flat = v.reshape(-1)
    out = ring_allreduce_int8(flat, "pod", 8)
    return out.reshape(v.shape)

got = ring(x)
want = jnp.broadcast_to(jnp.sum(x, axis=0, keepdims=True), x.shape)
rel = float(jnp.max(jnp.abs(got - want)) / (jnp.max(jnp.abs(want)) + 1e-9))
assert rel < 0.05, rel
print("RING_OK", rel)
"""


def test_int8_ring_allreduce_matches_psum():
    """Run on 8 virtual devices in a subprocess (tests keep 1 device)."""
    out = subprocess.run(
        [sys.executable, "-c", _RING_SCRIPT, "src"],
        capture_output=True, text=True, timeout=300, cwd=".",
    )
    assert "RING_OK" in out.stdout, out.stdout + out.stderr


def test_train_step_with_compression_converges():
    from repro.launch.train import train

    out = train("gemma-2b", steps=30, batch=8, seq=64, smoke=True,
                compress_grads=True, log_fn=lambda *_: None)
    # compressed training still converges (error feedback at work)
    head = float(np.mean(out["losses"][:5]))
    tail = float(np.mean(out["losses"][-5:]))
    assert tail < head - 0.1, (head, tail)
