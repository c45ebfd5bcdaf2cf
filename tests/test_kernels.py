"""Per-kernel allclose vs the pure-jnp oracles (interpret mode on CPU),
sweeping shapes, dtypes and feature flags."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.kernel import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.slstm_cell.kernel import slstm_cell
from repro.kernels.slstm_cell.ref import slstm_cell_ref
from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_scan_ref


def _tol(dtype):
    return 3e-2 if dtype == jnp.bfloat16 else 2e-4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,sq,skv,h,kv,hd,causal,window,softcap,bq,bk",
    [
        (2, 128, 128, 4, 2, 32, True, 0, 0.0, 64, 64),
        (1, 256, 256, 2, 2, 64, True, 48, 0.0, 64, 64),
        (1, 128, 128, 4, 1, 32, False, 0, 0.0, 64, 32),
        (1, 128, 128, 2, 2, 32, True, 0, 30.0, 32, 64),
        (2, 64, 64, 8, 8, 16, True, 0, 0.0, 32, 32),
        (1, 64, 64, 4, 4, 128, True, 0, 0.0, 64, 64),
    ],
)
def test_flash_attention_vs_ref(b, sq, skv, h, kv, hd, causal, window,
                                softcap, bq, bk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, sq, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, skv, kv, hd), dtype)
    v = jax.random.normal(ks[2], (b, skv, kv, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, bq=bq, bk=bk, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window,
                        softcap=softcap)
    err = jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))
    assert float(err) < _tol(dtype), float(err)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,l,h,kv,hd,softcap,bk,frac",
    [
        (2, 256, 8, 2, 32, 0.0, 64, 0.7),
        (1, 512, 4, 4, 64, 0.0, 128, 0.5),
        (1, 256, 8, 1, 32, 30.0, 64, 0.9),
        (2, 128, 16, 4, 16, 0.0, 32, 1.0),
    ],
)
def test_decode_attention_vs_ref(b, l, h, kv, hd, softcap, bk, frac, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, 1, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, l, kv, hd), dtype)
    v = jax.random.normal(ks[2], (b, l, kv, hd), dtype)
    valid = jnp.arange(l) < int(l * frac)
    out = decode_attention(q, k, v, valid, softcap=softcap, bk=bk,
                           interpret=True)
    ref = decode_attention_ref(q, k, v, valid, softcap=softcap)
    err = jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))
    assert float(err) < _tol(dtype), float(err)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,h,p,n,chunk",
    [
        (2, 128, 4, 16, 8, 32),
        (1, 256, 2, 64, 64, 64),
        (2, 64, 8, 32, 16, 16),
    ],
)
def test_ssd_scan_vs_ref(b, s, h, p, n, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))).astype(dtype)
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    bm = jax.random.normal(ks[3], (b, s, n), dtype)
    cm = jax.random.normal(ks[4], (b, s, n), dtype)
    out = ssd_scan(x, dt, a, bm, cm, chunk=chunk, interpret=True)
    ref = ssd_scan_ref(x, dt, a, bm, cm, chunk=chunk)
    scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32)))) + 1e-6
    err = float(
        jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))
    ) / scale
    assert err < _tol(dtype), err


def test_flash_attention_matches_model_attention_path():
    """The kernel agrees with the model's XLA attention layer."""
    from repro.models import layers
    from repro.models.config import ArchConfig

    cfg = ArchConfig(name="t", family="dense", n_layers=1, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64,
                     head_dim=16)
    p = layers.attention_init(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 64, 64), jnp.float32)

    q, k, v = layers._qkv(p, cfg, x)
    pos = jnp.arange(64)[None, :]
    q = layers.rope(q, pos, cfg.rope_theta)
    k = layers.rope(k, pos, cfg.rope_theta)
    out_kernel = flash_attention(q, k, v, causal=True, bq=32, bk=32,
                                 interpret=True)
    out_ref = attention_ref(q, k, v, causal=True)
    err = jnp.max(jnp.abs(out_kernel - out_ref))
    assert float(err) < 1e-4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,h,d,chunk", [
    (2, 64, 3, 16, 32), (1, 128, 2, 32, 64), (2, 96, 4, 8, 16),
])
def test_slstm_cell_vs_ref(b, t, h, d, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(5), 8)
    zx, ix, fx, ox = (
        jax.random.normal(ks[i], (b, t, h, d), dtype) for i in range(4)
    )
    rz, ri, rf, ro = (
        jax.random.normal(ks[4 + i], (h, d, d), dtype) * 0.2
        for i in range(4)
    )
    out = slstm_cell(zx, ix, fx, ox, rz, ri, rf, ro, chunk=chunk,
                     interpret=True)
    ref = slstm_cell_ref(zx, ix, fx, ox, rz, ri, rf, ro)
    err = jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))
    assert float(err) < (5e-2 if dtype == jnp.bfloat16 else 1e-4)


def _device_chain_scores(spec, durs, lens):
    from repro.core.family_eval import _chains_program

    C, _, L = durs.shape
    with jax.enable_x64(True):
        walk = _chains_program(spec, C, L)
        return np.asarray(walk(jnp.asarray(durs), jnp.asarray(lens)))


@pytest.mark.parametrize("spec_name", ["A30", "A100", "TPU"])
@pytest.mark.parametrize("C,L,integer", [
    (1, 1, False), (3, 7, True), (8, 21, False), (13, 40, True),
])
def test_chains_makespan_vs_ref_bit_exact(spec_name, C, L, integer):
    """The vectorized evaluator's jitted chain walk against the numpy
    reference.  Unlike the model kernels, the contract is bit-exactness,
    not a tolerance: phase-2 winner selection breaks EPS ties by index,
    so a single ulp could flip a winner."""
    from repro.core.device_spec import A30, A100, TPU_POD_256
    from repro.core.timing import chains_makespan_batch

    spec = {"A30": A30, "A100": A100, "TPU": TPU_POD_256}[spec_name]
    N = len(spec.nodes)
    rng = np.random.default_rng(C * 31 + L)
    lens = rng.integers(0, L + 1, size=(C, N)).astype(np.int32)
    lens[0] = 0  # empty candidate: makespan 0 by definition
    durs = np.zeros((C, N, L))
    for c in range(C):
        for j in range(N):
            k = lens[c, j]
            vals = rng.uniform(0.5, 4.0, size=k)
            if integer:  # tie-dense chains stress the (when, seq) order
                vals = np.floor(vals * 2.0) / 2.0
            durs[c, j, :k] = vals
    ref = chains_makespan_batch(spec, durs, lens)
    out = _device_chain_scores(spec, durs, lens)
    assert out.dtype == np.float64
    assert np.array_equal(ref, out)


def test_chains_makespan_empty_candidates():
    from repro.core.device_spec import A100

    N = len(A100.nodes)
    out = _device_chain_scores(
        A100, np.zeros((8, N, 4)), np.zeros((8, N), dtype=np.int32)
    )
    assert out.tolist() == [0.0] * 8
