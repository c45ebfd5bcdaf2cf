"""Logical-axis sharding rules (DP / FSDP / TP / EP / SP).

Model code annotates parameters and activations with *logical* axis names
("embed", "vocab", "heads", "ff", "experts", "batch", "seq", …).  A
:class:`ShardingRules` table resolves logical names to physical mesh axes,
per architecture — e.g. attention heads shard over ``model`` only when the
head count divides the axis; experts use EP when they divide it and fall
back to intra-expert tensor parallelism otherwise (DESIGN.md §5).

The resolution is dependency-light so the scheduler/cost model can use it
without touching jax device state; actual ``NamedSharding`` objects are
built only when a mesh is supplied.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ArchConfig

# mesh axis names used across the framework
POD, DATA, MODEL = "pod", "data", "model"


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """logical axis -> tuple of mesh axes (or () for replicated)."""

    rules: Mapping[str, tuple[str, ...]]
    mesh_axes: tuple[str, ...]

    def spec(self, *logical: str | None) -> P:
        parts = []
        for name in logical:
            if name is None:
                parts.append(None)
                continue
            axes = self.rules.get(name, ())
            if not axes:
                parts.append(None)
            elif len(axes) == 1:
                parts.append(axes[0])
            else:
                parts.append(tuple(axes))
        return P(*parts)

    def sharding(self, mesh: Mesh, *logical: str | None) -> NamedSharding:
        return NamedSharding(mesh, self.spec(*logical))


def _axis_size(mesh_shape: Mapping[str, int], axis: str) -> int:
    return mesh_shape.get(axis, 1)


def make_rules(
    cfg: ArchConfig,
    mesh_shape: Mapping[str, int],
    fsdp: bool | None = None,
    seq_shard: bool = True,
    batch_size: int | None = None,
) -> ShardingRules:
    """Build the rule table for one architecture on one mesh shape.

    Args:
      cfg: architecture.
      mesh_shape: e.g. {"data": 16, "model": 16} or with "pod".
      fsdp: shard parameters' non-TP dimension over ``data`` (ZeRO-3-style).
        Default: on when the replicated parameter bytes exceed ~1.5 GiB/chip.
      seq_shard: sequence-parallel the residual stream over ``model``.
      batch_size: when given, the ``batch`` logical axis only keeps the
        data axes it divides (long_500k decodes a single stream: batch=1
        cannot data-shard, so the data axes idle — visible in §Roofline).
    """
    model = _axis_size(mesh_shape, MODEL)
    data_axes = tuple(a for a in (POD, DATA) if a in mesh_shape)
    if batch_size is not None:
        kept: tuple[str, ...] = ()
        # keep the largest prefix of (pod, data) whose product divides batch
        for i in range(len(data_axes), 0, -1):
            prod = 1
            for a in data_axes[:i]:
                prod *= mesh_shape[a]
            if batch_size % prod == 0:
                kept = data_axes[:i]
                break
        data_axes = kept

    heads_ok = cfg.n_heads % model == 0
    kv_ok = cfg.n_kv_heads % model == 0 and heads_ok
    ff_ok = (cfg.d_ff % model == 0) if cfg.d_ff else False
    vocab_ok = cfg.padded_vocab() % model == 0
    experts_ok = cfg.is_moe and cfg.n_experts_padded % model == 0
    expert_ff_ok = cfg.is_moe and cfg.expert_d_ff % model == 0
    dinner_ok = cfg.family in ("ssm", "hybrid") and cfg.d_inner % model == 0

    if fsdp is None:
        repl_bytes = cfg.param_count() * 2 / max(model, 1)
        fsdp = repl_bytes > 1.5 * 2**30

    fsdp_axes: tuple[str, ...] = (DATA,) if (fsdp and DATA in mesh_shape) else ()

    rules: dict[str, tuple[str, ...]] = {
        # --- parameters ---
        "embed": fsdp_axes,                    # d_model dim of most weights
        "vocab": (MODEL,) if vocab_ok else (),
        "heads": (MODEL,) if heads_ok else (),
        "kv_heads": (MODEL,) if kv_ok else (),
        "head_dim": (),
        "ff": (MODEL,) if ff_ok else (),
        "experts": (MODEL,) if experts_ok else (),
        # EP when experts divide the axis, otherwise intra-expert TP
        "expert_ff": () if experts_ok else
                     ((MODEL,) if expert_ff_ok else ()),
        "act_expert_ff": () if experts_ok else
                         ((MODEL,) if expert_ff_ok else ()),
        "d_inner": (MODEL,) if dinner_ok else (),
        "ssm_state": (),
        "conv": (),
        "ssm_heads": (MODEL,) if (
            cfg.family in ("ssm", "hybrid")
            and (cfg.d_inner // 64) % model == 0
        ) else (),
        "act_ssm_heads": (MODEL,) if (
            cfg.family in ("ssm", "hybrid")
            and (cfg.d_inner // 64) % model == 0
        ) else (),
        # --- activations ---
        "batch": data_axes,
        "seq": (MODEL,) if seq_shard else (),
        "act_heads": (MODEL,) if heads_ok else (),
        # H5: when heads cannot shard, shard attention *queries* over the
        # model axis instead (k/v stay whole — tiny under MQA/GQA): each
        # device scores only its query rows, removing the 16x-replicated
        # [*, S, S] attention work on few-head archs (gemma-2b, whisper)
        "q_seq": () if heads_ok else ((MODEL,) if seq_shard else ()),
        "act_ff": (MODEL,) if ff_ok else (),
        "act_vocab": (MODEL,) if vocab_ok else (),
        "act_d_inner": (MODEL,) if dinner_ok else (),
        # flash-decoding-style KV sharding (§Perf H4): when the kv heads
        # cannot shard over the model axis, shard the cache LENGTH instead —
        # each device scores its KV chunk and the softmax merge becomes a
        # pair of tiny cross-shard reductions.  Without this, archs like
        # qwen1.5-110b (kv=8) replicate a 121 GiB cache per device.
        "kv_len": () if kv_ok else (MODEL,),
    }
    return ShardingRules(rules=rules, mesh_axes=tuple(mesh_shape))


# ---------------------------------------------------------------------------
# activation constraint helper: models call logical() inside jit; it is a
# no-op outside a mesh context so smoke tests on 1 CPU device do not shard.
# ---------------------------------------------------------------------------

# per thread: the live executor traces jobs for disjoint instances
# concurrently, one thread per instance
_ACTIVE = threading.local()


def _active_rules() -> list[ShardingRules | None]:
    stack = getattr(_ACTIVE, "rules", None)
    if stack is None:
        stack = _ACTIVE.rules = [None]
    return stack


class use_rules:
    """Context manager installing rules for ``logical`` constraints."""

    def __init__(self, rules: ShardingRules | None):
        self.rules = rules

    def __enter__(self):
        _active_rules().append(self.rules)
        return self.rules

    def __exit__(self, *exc):
        _active_rules().pop()
        return False


def logical(x: jax.Array, *names: str | None) -> jax.Array:
    """Apply a with_sharding_constraint for the active rule table (no-op
    when no rules are installed, e.g. single-device smoke tests)."""
    rules = _active_rules()[-1]
    if rules is None:
        return x
    spec = rules.spec(*names)
    return jax.lax.with_sharding_constraint(x, spec)


def param_shardings(
    param_specs,  # pytree of tuple[str|None, ...]
    rules: ShardingRules,
    mesh: Mesh,
):
    """Resolve a pytree of logical param specs into NamedShardings."""
    return jax.tree.map(
        lambda spec: rules.sharding(mesh, *spec),
        param_specs,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(e, (str, type(None))) for e in x),
    )
