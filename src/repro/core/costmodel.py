"""Roofline cost model: job time vs pod-slice count for the TPU adaptation.

This is the framework's ``t_i(s)`` (the paper profiles its tasks on each
MIG size; we derive ours from the same roofline terms the dry-run reports —
§Roofline in EXPERIMENTS.md cross-checks the two).

A pod slice = 32 chips ((2,16) block); a size-``s`` instance is a
(2s, 16) sub-mesh: the model axis stays 16 (TP/EP collectives over ICI),
the data axis grows with s.  Per step:

  compute    = FLOPs / (chips · peak · eff)
  memory     = bytes touched per chip / HBM bw, times a *spill* penalty
               when the working set exceeds HBM — remat/offload traffic
               grows sharply, which is what makes narrow instances
               super-linearly slow (the TPU analogue of the paper's §2.4
               memory-bound MIG superscaling)
  collective = TP/EP activation reductions + DP gradient reduction over ICI

  t(s) = (max of the three) · steps + dispatch overhead

Times are monotone non-increasing in ``s`` (paper monotony point 1) while
*work* ``s·t(s)`` is not monotone when spill is in play — exactly the
regime FAR's allocation family is designed for.
"""

from __future__ import annotations

import dataclasses

from repro.core.device_spec import DeviceSpec, TPU_POD_256
from repro.core.problem import Task
from repro.models.config import ArchConfig, ShapeConfig

# hardware constants (DESIGN.md §6)
PEAK_FLOPS = 197e12
HBM_BW = 819e9
HBM_CAP = 16 * 2**30
ICI_BW = 100e9           # per chip budget (2 link-pairs x 50 GB/s)
COMPUTE_EFF = 0.5        # achievable fraction of peak on dense matmuls
MODEL_AXIS = 16


@dataclasses.dataclass(frozen=True)
class Job:
    """A schedulable unit: run `steps` steps of (arch × shape)."""

    id: int
    cfg: ArchConfig
    shape: ShapeConfig
    steps: int
    name: str = ""
    checkpoint_every: int = 50

    @property
    def label(self) -> str:
        return self.name or f"{self.cfg.name}/{self.shape.name}×{self.steps}"


def step_time(cfg: ArchConfig, shape: ShapeConfig, slices: int,
              chips_per_slice: int = 32) -> float:
    """Seconds per step on a size-``slices`` instance."""
    chips = slices * chips_per_slice
    # below one full model axis (a 1-4 chip host) the model axis shrinks
    # to the chips there are, and data parallelism is at least 1
    model_axis = min(MODEL_AXIS, chips)
    dp = max(chips // model_axis, 1)
    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * (
        shape.seq_len if shape.kind != "decode" else 1
    )
    train = shape.kind == "train"
    factor = 6 if train else 2
    flops = factor * n_active * tokens
    # attention flops (quadratic part) — matters for prefill_32k
    if shape.kind != "decode" and cfg.family not in ("ssm",):
        att_layers = (
            cfg.n_layers // (cfg.shared_attn_every or cfg.n_layers)
            if cfg.family == "hybrid" else cfg.n_layers
        )
        window = cfg.sliding_window or 0
        if cfg.local_global:
            n_glob = cfg.n_layers // (cfg.local_global + 1)
            n_loc = cfg.n_layers - n_glob
            eff_ctx = (
                n_glob * shape.seq_len + n_loc * min(window, shape.seq_len)
            ) / cfg.n_layers
            att_layers = cfg.n_layers
        else:
            eff_ctx = shape.seq_len
        qk = cfg.n_heads * cfg.resolved_head_dim
        # QK^T + PV: 2 matmuls × 2 MAC × causal/2, per attention layer
        flops += (3 if train else 1) * 4 * tokens * (eff_ctx / 2) * qk \
            * att_layers

    t_compute = flops / (chips * PEAK_FLOPS * COMPUTE_EFF)

    # --- memory ------------------------------------------------------------
    param_bytes = n_params * 2
    opt_bytes = n_params * 8 if train else 0
    act_bytes_per_chip = (
        tokens / dp * cfg.d_model * 2 * cfg.n_layers * 4 / model_axis
    )
    if shape.kind == "decode":
        # KV-cache / state read dominates
        if cfg.family in ("ssm", "hybrid"):
            state = cfg.n_layers * shape.global_batch * cfg.d_inner * 64 * 4
            act_bytes_per_chip = state / chips
        else:
            kv = (
                2 * cfg.n_layers * shape.global_batch * shape.seq_len
                * cfg.n_kv_heads * cfg.resolved_head_dim * 2
            )
            if cfg.local_global:
                n_glob = cfg.n_layers // (cfg.local_global + 1)
                kv = kv * n_glob / cfg.n_layers  # local caches are tiny
            act_bytes_per_chip = kv / chips
    weight_reads_per_chip = (param_bytes * (3 if train else 1)) / chips
    bytes_per_chip = weight_reads_per_chip + act_bytes_per_chip

    # working set per chip and the spill penalty (applied to the whole
    # step below: offload/remat traffic stalls compute too)
    need = (param_bytes + opt_bytes) / chips + act_bytes_per_chip
    spill = max(1.0, (need / HBM_CAP) ** 2)  # quadratic once over capacity
    t_memory = bytes_per_chip / HBM_BW

    # --- collectives --------------------------------------------------------
    act_ar = 2 * (tokens / dp) * cfg.d_model * 2 * cfg.n_layers * 2
    if shape.kind == "decode":
        act_ar = 2 * (tokens / dp) * cfg.d_model * 2 * cfg.n_layers * 2
    grad_ar = 2 * param_bytes / dp if train else 0.0
    t_coll = (act_ar + grad_ar) / ICI_BW

    return max(t_compute, t_memory, t_coll) * spill


def job_time(job: Job, slices: int, chips_per_slice: int = 32,
             dispatch_overhead: float = 2.0) -> float:
    return (
        step_time(job.cfg, job.shape, slices, chips_per_slice) * job.steps
        + dispatch_overhead
    )


def job_to_task(job: Job, spec: DeviceSpec = TPU_POD_256) -> Task:
    """Profile a job on every instance size of ``spec`` (the paper's t_i)."""
    times = {
        s: job_time(job, s, spec.chips_per_slice) for s in spec.sizes
    }
    # enforce monotone non-increasing times (paper monotony point 1) in the
    # face of modelling noise
    sizes = sorted(times)
    for a, b in zip(sizes, sizes[1:]):
        times[b] = min(times[b], times[a])
    return Task(id=job.id, times=times, name=job.label)
