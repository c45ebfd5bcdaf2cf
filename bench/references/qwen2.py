"""Plain float32 reference of a Qwen2-style dense decoder, and its control.

Written from the published architecture (RMSNorm, rotary attention with
grouped key/value heads and QKV bias, SwiGLU MLP), in straightforward
``jax.numpy`` at ``Precision.HIGHEST``.  It imports nothing of the system
under test.  What it needs of the deployment comes from the configuration
file: the published sizes under ``model`` and the conventions of the
weights as they are served under ``weights``:

* ``weights.key`` and the split recipe below: random weights are drawn
  from ``jax.random.key(key)`` split as embedding / blocks / final, each
  block into 4 (attention, -, MLP, -), attention into wq/wk/wv/wo and the
  MLP into wi/wg/wo; every matrix is ``normal * fan_in**-0.5`` rounded to
  bfloat16; biases are zero;
* ``weights.norm_weight``: every RMSNorm weight (the served model keeps
  ``scale = 1`` and multiplies by ``1 + scale``);
* ``weights.padded_vocab``: rows of the embedding and columns of the
  output head, padded past ``vocab_size``;
* ``weights.untied_head``: the output head is a matrix of its own (drawn
  as below) rather than the embedding's transpose.

The reference runs layer by layer (each layer's weights are made, used on
every sampled row, and dropped), one row at a time, and computes logits
only at the positions whose next token was served.  Its outcome is the
gap, at each such position, between the reference's best logit and its
logit of the served token: 0 where the served token is the reference's
first choice.

The control is the same forward with every linear layer's operands
rounded to float8 (e4m3, scaled by the row's or column's absolute maximum):
the step below the bfloat16 the deployment states.

``job_flops`` counts the model FLOPs of a served job from the same
published sizes; ``bench/metrics/job_mfu.live.py`` reads it through the
configuration's ``reference``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    f: int
    layers: int
    heads: int
    kv: int
    hd: int
    vocab: int          # ids the traffic draws from
    vocab_rows: int     # rows of the (padded) embedding
    theta: float
    eps: float
    norm_weight: float
    key: int
    untied: bool

    @classmethod
    def from_config(cls, config: dict) -> "Dims":
        m, w = config["model"], config["weights"]
        heads = m["num_attention_heads"]
        return cls(
            d=m["hidden_size"], f=m["intermediate_size"],
            layers=m["num_hidden_layers"], heads=heads,
            kv=m["num_key_value_heads"],
            hd=m.get("head_dim", m["hidden_size"] // heads),
            vocab=m["vocab_size"], vocab_rows=w["padded_vocab"],
            theta=float(m["rope_theta"]), eps=float(m["rms_norm_eps"]),
            norm_weight=float(w["norm_weight"]), key=int(w["key"]),
            untied=bool(w["untied_head"]),
        )


def _normal(key, shape, fan_in):
    scale = np.float32(1.0 / np.sqrt(fan_in))
    w = (jax.random.normal(key, shape, jnp.float32) * scale)
    return w.astype(jnp.bfloat16).astype(jnp.float32)


def _root_keys(dm: Dims):
    """(embedding key, one key per layer)."""
    k_emb, k_blocks, _ = jax.random.split(jax.random.key(dm.key), 3)
    return k_emb, jax.random.split(k_blocks, dm.layers)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _embedding(dm: Dims, k_emb, which: int):
    """0: the input table (rows, d); 1: the output head (d, rows)."""
    ks = jax.random.split(k_emb, 2)
    if which == 0:
        return _normal(ks[0], (dm.vocab_rows, dm.d), dm.d)
    return _normal(ks[1], (dm.d, dm.vocab_rows), dm.d)


@functools.partial(jax.jit, static_argnums=(0,))
def _layer_weights(dm: Dims, key):
    ks = jax.random.split(key, 4)
    ka = jax.random.split(ks[0], 4)
    km = jax.random.split(ks[2], 3)
    d, q, kv = dm.d, dm.heads * dm.hd, dm.kv * dm.hd
    return {
        "wq": _normal(ka[0], (d, q), d), "wk": _normal(ka[1], (d, kv), d),
        "wv": _normal(ka[2], (d, kv), d), "wo": _normal(ka[3], (q, d), q),
        "wi": _normal(km[0], (d, dm.f), d), "wg": _normal(km[1], (d, dm.f), d),
        "wd": _normal(km[2], (dm.f, d), dm.f),
    }


def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _linear(x, w, low: bool):
    if low:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _norm(dm: Dims, x):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + dm.eps) * dm.norm_weight


def _rope(dm: Dims, x):
    """Rotary embedding over halves; x (S, heads, hd) at positions 0..S-1."""
    half = dm.hd // 2
    freqs = dm.theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(dm: Dims, w, x, low: bool):
    """One decoder layer on one row, x (S, d)."""
    s = x.shape[0]
    h = _norm(dm, x)
    q = _rope(dm, _linear(h, w["wq"], low).reshape(s, dm.heads, dm.hd))
    k = _rope(dm, _linear(h, w["wk"], low).reshape(s, dm.kv, dm.hd))
    v = _linear(h, w["wv"], low).reshape(s, dm.kv, dm.hd)
    g = dm.heads // dm.kv
    k = jnp.repeat(k, g, axis=1)       # query head j reads kv head j // g
    v = jnp.repeat(v, g, axis=1)
    att = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(dm.hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", att, v, precision=HI).reshape(s, -1)
    x = x + _linear(o, w["wo"], low)
    h = _norm(dm, x)
    a = jax.nn.silu(_linear(h, w["wg"], low)) * _linear(h, w["wi"], low)
    return x + _linear(a, w["wd"], low)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _logits(dm: Dims, head, x, first: int, low: bool):
    """Logits at positions first.. of one row."""
    return _linear(_norm(dm, x[first:]), head, low)


@dataclasses.dataclass
class Row:
    """One served sequence: its prompt and the tokens the system served."""

    prompt: np.ndarray   # (P,) int
    served: np.ndarray   # (G,) int


def logit_gaps(config: dict, rows: list[Row], control: bool = False
               ) -> dict:
    """At every served position of ``rows``, the gap by which the served
    token's reference logit lies below the reference's best.

    With ``control``, also the gap of the token that the float8 forward
    puts first at the same positions.  Returns ``{"served": array,
    "control": array | None}``, one entry per served position.
    """
    dm = Dims.from_config(config)
    k_emb, layer_keys = _root_keys(dm)
    seqs = [np.concatenate([r.prompt, r.served[:-1]]).astype(np.int32)
            for r in rows]
    table = _embedding(dm, k_emb, 0)
    xs = [table[jnp.asarray(s)] for s in seqs]
    del table
    lows = list(xs) if control else None
    for i in range(dm.layers):
        w = _layer_weights(dm, layer_keys[i])
        xs = [_layer(dm, w, x, False) for x in xs]
        if control:
            lows = [_layer(dm, w, x, True) for x in lows]
        del w
    head = (_embedding(dm, k_emb, 1) if dm.untied
            else _embedding(dm, k_emb, 0).T)
    served, low_gaps = [], []
    for k, (r, x) in enumerate(zip(rows, xs)):
        first = len(r.prompt) - 1
        at = np.arange(len(r.served))
        ref = np.asarray(_logits(dm, head, x, first, False))
        best = ref.max(axis=-1)
        served.append(best - ref[at, r.served])
        if control:
            pick = np.asarray(_logits(dm, head, lows[k], first, True))
            low_gaps.append(best - ref[at, pick.argmax(axis=-1)])
    return {"served": np.concatenate(served),
            "control": np.concatenate(low_gaps) if control else None}


# -- model FLOPs of a served job ---------------------------------------------
#
# Counts what the model needs and nothing the implementation adds: every
# linear layer (2 FLOPs per multiply-add), causal attention (the score and
# value products over the positions each query may see) and the output
# head at each position whose next token is served.  The embedding lookup
# and the elementwise work are not counted.


def _sizes(model: dict):
    d = model["hidden_size"]
    heads = model["num_attention_heads"]
    hd = model.get("head_dim", d // heads)
    kv = model["num_key_value_heads"]
    per_layer = d * heads * hd + 2 * d * kv * hd + heads * hd * d \
        + 3 * d * model["intermediate_size"]
    return model["num_hidden_layers"], per_layer, heads * hd, \
        d * model["vocab_size"]


def job_flops(model: dict, batch: int, prompt: int, gen: int) -> float:
    """FLOPs of prefilling ``prompt`` tokens and serving ``gen`` tokens
    greedily, for each of ``batch`` rows (the first served token comes
    from the prefill, each later one from a decode step)."""
    layers, linear, qk, head = _sizes(model)
    # prefill: every position through every layer; query i sees i + 1 keys
    seen = prompt * (prompt + 1) // 2
    flops = 2 * layers * linear * prompt + 4 * layers * qk * seen + 2 * head
    # decode: the step at position p sees p + 1 keys
    for p in range(prompt, prompt + gen - 1):
        flops += 2 * layers * linear + 4 * layers * qk * (p + 1) + 2 * head
    return float(batch * flops)
