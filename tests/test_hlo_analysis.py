"""HLO analyzer: loop-aware FLOP counting matches analytic counts."""

import subprocess
import sys

from repro.launch.hlo_analysis import (
    _split_computations,
    _symbol_table,
    _trip_count,
    analyze,
)

_PROBE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import sys; sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 4), ("data", "model"))
D, F, L = 256, 512, 8

def loss(params, x):
    def body(c, p):
        h = jnp.dot(c, p["w1"], preferred_element_type=jnp.float32)
        h = h.astype(jnp.bfloat16)
        c = jnp.dot(jax.nn.relu(h), p["w2"],
                    preferred_element_type=jnp.float32).astype(jnp.bfloat16)
        c = jax.lax.with_sharding_constraint(c, P("data", None, "model"))
        return c, None
    x, _ = jax.lax.scan(body, x, params)
    return jnp.sum(x.astype(jnp.float32))

params = {"w1": jax.ShapeDtypeStruct((L, D, F), jnp.bfloat16),
          "w2": jax.ShapeDtypeStruct((L, F, D), jnp.bfloat16)}
x = jax.ShapeDtypeStruct((16, 64, D), jnp.bfloat16)
psh = {"w1": NamedSharding(mesh, P(None, None, "model")),
       "w2": NamedSharding(mesh, P(None, "model", None))}
xsh = NamedSharding(mesh, P("data", None, None))
with mesh:
    comp = jax.jit(jax.grad(loss),
                   in_shardings=(psh, xsh)).lower(params, x).compile()
res = analyze(comp.as_text())
analytic = 2 * 4 * 64 * 256 * 128 * 2 * 8 * 3   # per-device fwd+bwd
ratio = res["flops_per_device"] / analytic
assert 0.95 < ratio < 1.3, ratio
assert res["collective_total"] > 0
print("ANALYZE_OK", ratio)
"""


def test_loop_aware_flops_match_analytic():
    out = subprocess.run(
        [sys.executable, "-c", _PROBE, "src"],
        capture_output=True, text=True, timeout=600, cwd=".",
    )
    assert "ANALYZE_OK" in out.stdout, out.stdout[-2000:] + out.stderr[-2000:]


def test_parser_units():
    hlo = """
HloModule test

%fused_computation (param_0: f32[8,16]) -> f32[8,16] {
  %param_0 = f32[8,16]{1,0} parameter(0)
  ROOT %e = f32[8,16]{1,0} exponential(%param_0)
}

%cond (p: (s32[], f32[8,16])) -> pred[] {
  %p = (s32[], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%body (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p = (s32[], f32[8,16]) parameter(0)
  %x = f32[8,16]{1,0} get-tuple-element(%p), index=1
  %w = f32[16,16]{1,0} constant({...})
  %d = f32[8,16]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], f32[8,16]) tuple(%i, %d)
}

ENTRY %main (a: f32[8,16]) -> f32[8,16] {
  %a = f32[8,16]{1,0} parameter(0)
  %f = f32[8,16]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation
  %init = (s32[], f32[8,16]) tuple()
  %w = (s32[], f32[8,16]) while(%init), condition=%cond, body=%body
  ROOT %g = f32[8,16]{1,0} get-tuple-element(%w), index=1
}
"""
    comps = _split_computations(hlo)
    assert set(comps) >= {"fused_computation", "cond", "body", "main"}
    assert _trip_count(comps["cond"]) == 5
    res = analyze(hlo)
    # dot flops: 2*8*16*16 = 4096 per iteration, times 5 trips
    assert res["flops_per_device"] == 4096 * 5
