"""Unified scheduling service API: policies, config and plan results.

The paper frames MIG scheduling as one problem with many strategies — FAR
(§3), MISO-OPT and fixed partitions (§6.5), online greedy placement (§7).
This module is the surface that makes them interchangeable:

* :class:`SchedulerConfig` — one frozen knob object replacing the boolean
  kwarg sprawl that had accumulated on ``schedule_batch`` (refinement
  depth, pruning, engine selection, EPS, seam mode, latency budget, seed);
* :class:`PlanResult` — the unified return type every strategy adapts
  into (schedule, makespan, assignment, per-phase wall time, reconfig
  events, policy-specific extras);
* :class:`SchedulerPolicy` / :func:`register_policy` / :func:`get_policy`
  — a string-keyed registry so consumers (benchmarks, the multi-batch
  driver, the serving facade) run *any* strategy as one loop over names.

Policies self-register where they are implemented (``far.py``,
``baselines.py``, ``online.py``, ``multibatch.py``); :func:`get_policy`
imports those modules lazily so ``import repro.core.policy`` alone never
drags in the whole scheduler stack.
"""

from __future__ import annotations

import dataclasses
import importlib
import time
from typing import Callable, Protocol, Sequence, runtime_checkable

from repro.core.device_spec import DeviceSpec
from repro.core.problem import EPS, Schedule, Task, bind_tasks, validate_schedule
from repro.core.repartition import Assignment
from repro.core.spans import span


#: valid SchedulerConfig.evaluator values (the family-evaluator registry
#: in repro.core.family_eval may grow beyond these for custom plugins;
#: config validation names only the built-ins plus "auto")
_EVALUATOR_CHOICES = frozenset(
    {"sequential", "incremental", "parallel", "vectorized", "auto"}
)


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """All scheduling knobs in one immutable value.

    The first block mirrors the legacy ``schedule_batch`` booleans; the
    second configures seam concatenation (multi-batch / tail-aware plans);
    the third is the online-serving latency budget consumed by
    :class:`~repro.core.service.SchedulingService`.
    """

    # -- FAR phases (legacy schedule_batch kwargs) --------------------------
    refine: bool = True               # phase-3 move/swap refinement
    max_refine_iterations: int = 64
    prune: bool = True                # admissible phase-2 family pruning
    deep_refine: bool = False         # beyond-paper exact greedy pass
    use_engine: bool = True           # incremental TimingEngine vs replays
    eps: float = EPS                  # float tolerance for comparisons
    # phase-2 family evaluator: "sequential" (one Algorithm-1 simulation
    # per candidate), "incremental" (compiled delta-replay of the shared
    # trajectory prefix), "parallel" (process-pool family sharding),
    # "vectorized" (chunked array-program scoring), or "auto" (the best
    # available tier for the batch size).  All evaluators return
    # bit-identical winners — see repro.core.family_eval.
    evaluator: str = "auto"
    # "auto" task-count floor override: when set, replaces the module
    # constants (AUTO_MIN_TASKS*) gating the accelerated evaluators, so
    # deployments on bigger boxes can tune dispatch without
    # monkeypatching.  None keeps the calibrated defaults.
    evaluator_floor: int | None = None
    # pool width for evaluator="parallel": 0 = one worker per CPU core;
    # 1 short-circuits to sequential scoring in-process.
    parallel_workers: int = 0

    # -- seam concatenation (tail-aware planning) ---------------------------
    concat_mode: str = "move_swap"    # "trivial" | "reverse" | "move_swap" | "auto"
    reverse: bool = False             # play this segment leaves-first (§4.2)

    # -- strategy-specific --------------------------------------------------
    partition: tuple | None = None    # fix-part: instances to pin (None -> 1s)
    seed: int | None = None           # reserved for randomized strategies
    # "auto-serve" meta-policy: batches at least this dense flush through
    # FAR, sparser ones through fix-part.  The threshold comes from the
    # BENCH_online policy sweep: FAR's molding wins on dense batches
    # (gap 0.5s, ~16-task flushes) while its reconfiguration overhead
    # loses to a pinned all-1s partition at sparse rates (gaps 2–8s,
    # <=5-task flushes, fix-part ratios 0.75–0.84 vs FAR).
    auto_dense_batch: int = 12

    # -- online serving (SchedulingService latency budget) ------------------
    max_wait_s: float = 0.25          # accumulate arrivals this long
    max_batch: int = 32               # flush earlier once this many queue up
    min_batch: int = 2                # smaller deadline flushes go online

    # -- deadline-aware serving (SchedulingService SLOs) --------------------
    # admission control for tasks submitted with a deadline whose
    # completion is provably unmeetable against the service's lower bound:
    # "none" accepts everything (deadlines only tracked for miss-rate),
    # "reject" refuses the task, "demote" accepts it best-effort (the
    # deadline is dropped, so it never counts as a miss).
    admission: str = "none"
    # tail re-planning: when a flush lands, placements that have not yet
    # started are pulled back and re-scheduled together with the arrivals
    # (running tasks are never moved; the no-replan plan is kept whenever
    # re-planning does not strictly improve the combined makespan).  With
    # replan on, online-fallback (trickle) flushes also try a withdrawn-
    # tail re-plan under the same strict-win rule.
    replan: bool = False
    # EDF within-batch ordering: before a flush commits, each planned
    # node chain is stably reordered earliest-deadline-first (deadline
    # carriers ahead of best-effort work; see multibatch.edf_order).
    # Chain ends — and therefore makespan, the seam tail and every
    # never-worse guarantee — are order-invariant, only per-task
    # completion times inside a chain move.  False = bit-identical to
    # the makespan-only commit order.
    edf: bool = False

    # -- fault tolerance (closed-loop runtime feedback) ---------------------
    # implicit straggler detection: a committed placement whose observed
    # runtime (via SchedulingService.report / poll observations) exceeds
    # straggler_factor * its profiled duration without a completion
    # report has its projected end stretched and the tail force-re-planned.
    # None disables detection — the pre-feedback open-loop behaviour.
    straggler_factor: float | None = None
    # retry policy (repro.core.faults.RetryPolicy) for tasks reported
    # failed: capped exponential backoff on the re-release time, optional
    # demotion.  None = no retries; a failed task is permanently failed.
    retry: object | None = None
    # straggler speculation (repro.core.faults.SpeculationPolicy): when a
    # straggler is flagged, race a backup attempt on the best alternative
    # placement; first finisher wins, the loser is cancelled.  None =
    # stretch-only straggler handling (the PR 6 behaviour, bit-identical).
    speculation: object | None = None
    # online profile calibration (repro.core.faults.ProfileCalibration):
    # EWMA duration-correction state fed by report(end=) and applied at
    # the policy boundary only — the stored tasks keep their raw profiles.
    # None = plan straight from the submitted profiles, bit-identically.
    calibration: object | None = None
    # profile transfer fallback: derive missing (device_kind, size)
    # profile entries from the nearest measured kind at submit time
    # (repro.core.problem.transfer_profile).  False = off (a task must
    # cover its devices, PR 5 behaviour); True enables derivation with
    # unit speed factors; a {device_kind: relative_speed} mapping scales
    # cross-kind transfers by speed[donor] / speed[target].
    profile_transfer: object = False

    def __post_init__(self):
        if self.straggler_factor is not None and self.straggler_factor <= 1.0:
            raise ValueError(
                f"SchedulerConfig.straggler_factor must exceed 1.0 (a "
                f"deviation factor), got {self.straggler_factor!r}"
            )
        if self.admission not in ("none", "reject", "demote"):
            raise ValueError(
                f"SchedulerConfig.admission must be 'none', 'reject' or "
                f"'demote', got {self.admission!r}"
            )
        if self.evaluator in _EVALUATOR_CHOICES:
            return
        # custom evaluators registered via family_eval.register_evaluator
        # are also accepted (imported lazily to keep `import policy` light)
        from repro.core.family_eval import EVALUATORS

        if self.evaluator not in EVALUATORS:
            raise ValueError(
                f"SchedulerConfig.evaluator must be one of "
                f"{sorted(_EVALUATOR_CHOICES | set(EVALUATORS))}, "
                f"got {self.evaluator!r}"
            )

    def replace(self, **changes) -> "SchedulerConfig":
        return dataclasses.replace(self, **changes)


#: the legacy ``schedule_batch`` boolean kwargs and the config field each
#: maps to — the deprecation shim names these in its warning.
LEGACY_KWARGS: dict[str, str] = {
    "refine": "refine",
    "max_refine_iterations": "max_refine_iterations",
    "prune": "prune",
    "deep_refine": "deep_refine",
    "use_engine": "use_engine",
}


@dataclasses.dataclass
class PlanResult:
    """What every registered policy returns from ``plan``.

    ``makespan`` is stored (not derived) so bound-only policies such as
    ``"lower-bound"`` can report one without a schedule; for every
    schedule-producing policy it equals ``schedule.makespan``.
    ``extras`` carries the policy-specific result the legacy entry point
    used to return (``FARResult`` under ``"far"``, the chosen partition
    under ``"partition"``, online placements under ``"placements"``, the
    seam ``ConcatResult`` under ``"concat"``).  The serving facade adds
    deadline extras onto each flush's plan: ``"deadlines"`` (task id ->
    deadline for the deadline-carrying tasks of the batch) and
    ``"deadline_slack"`` (task id -> deadline minus planned completion at
    flush time; negative = the plan already misses it).
    """

    policy: str
    schedule: Schedule
    makespan: float
    assignment: Assignment | None = None
    tail: object | None = None        # multibatch.Tail after a tail-aware plan
    elapsed_s: float = 0.0
    phase_s: dict[str, float] | None = None
    extras: dict = dataclasses.field(default_factory=dict)

    @property
    def reconfig_events(self) -> int:
        return len(self.schedule.reconfigs)

    def validate(
        self, tasks: Sequence[Task] | None = None, check_reconfig: bool = True
    ) -> None:
        validate_schedule(self.schedule, tasks, check_reconfig=check_reconfig)


@runtime_checkable
class SchedulerPolicy(Protocol):
    """The policy protocol: ``plan(tasks, spec, config, tail) -> PlanResult``."""

    name: str

    def plan(
        self,
        tasks: Sequence[Task],
        spec: DeviceSpec,
        config: SchedulerConfig | None = None,
        tail: object | None = None,
    ) -> PlanResult: ...


class BasePolicy:
    """Shared plumbing: timing, config defaulting and tail-aware splicing.

    Subclasses implement ``_plan_fresh(tasks, spec, config) -> PlanResult``
    for a cold device.  When ``tail`` (a :class:`~repro.core.multibatch.Tail`)
    is given, the fresh plan's assignment is spliced after it with
    :func:`~repro.core.multibatch.concatenate` under ``config.concat_mode``
    (direction from ``config.reverse``) and the result carries the new tail.
    """

    name = "?"

    def plan(
        self,
        tasks: Sequence[Task],
        spec: DeviceSpec,
        config: SchedulerConfig | None = None,
        tail: object | None = None,
    ) -> PlanResult:
        cfg = config or SchedulerConfig()
        with span("repro.plan", policy=self.name, tasks=len(tasks)):
            t0 = time.perf_counter()
            # instance-type-keyed profiles are lowered onto this device's
            # kind at the policy boundary (identity for size-keyed tasks)
            tasks = bind_tasks(tasks, spec)
            res = self._plan_fresh(tasks, spec, cfg)
            res.policy = self.name
            if tail is not None:
                if res.assignment is None:
                    raise ValueError(
                        f"policy {self.name!r} produced no assignment; "
                        "tail-aware planning is unsupported"
                    )
                from repro.core.multibatch import concatenate

                out = concatenate(
                    res.assignment, tail, mode=cfg.concat_mode,
                    reverse=cfg.reverse, use_engine=cfg.use_engine,
                )
                res.schedule = out.schedule
                res.makespan = out.schedule.makespan
                res.tail = out.tail
                res.extras["concat"] = out
            res.elapsed_s = time.perf_counter() - t0
        return res

    def _plan_fresh(
        self, tasks: Sequence[Task], spec: DeviceSpec, config: SchedulerConfig
    ) -> PlanResult:
        raise NotImplementedError


def assignment_from_schedule(schedule: Schedule) -> Assignment:
    """Adapt a bare :class:`Schedule` (MISO / FixPart output) into the
    tree-chain :class:`Assignment` the seam machinery consumes: per-node
    task lists in begin-time order."""
    tasks = {it.task.id: it.task for it in schedule.items}
    node_tasks = {
        key: [it.task.id for it in lst]
        for key, lst in schedule.by_node().items()
    }
    return Assignment(schedule.spec, tasks, node_tasks)


# -- registry ---------------------------------------------------------------

_REGISTRY: dict[str, Callable[[], SchedulerPolicy]] = {}
_INSTANCES: dict[str, SchedulerPolicy] = {}

#: modules whose import self-registers the built-in policies
_BUILTIN_MODULES = (
    "repro.core.far",
    "repro.core.baselines",
    "repro.core.online",
    "repro.core.multibatch",
    "repro.core.cluster",
)


def register_policy(name: str):
    """Class decorator: ``@register_policy("far")`` adds the policy class
    to the registry under ``name`` (instantiated lazily, one singleton)."""

    def deco(cls):
        cls.name = name
        _REGISTRY[name] = cls
        _INSTANCES.pop(name, None)
        return cls

    return deco


def _ensure_builtins() -> None:
    for mod in _BUILTIN_MODULES:
        importlib.import_module(mod)


def get_policy(name: str) -> SchedulerPolicy:
    """Look up a registered policy instance by name."""
    if name not in _REGISTRY:
        _ensure_builtins()
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown scheduling policy {name!r}; "
            f"available: {', '.join(sorted(_REGISTRY))}"
        )
    if name not in _INSTANCES:
        _INSTANCES[name] = _REGISTRY[name]()
    return _INSTANCES[name]


def available_policies() -> list[str]:
    """Sorted names of every registered policy."""
    _ensure_builtins()
    return sorted(_REGISTRY)


@register_policy("auto-serve")
class AutoServePolicy:
    """Per-flush policy selector driven by batch density.

    The BENCH_online policy sweep shows a regime split: FAR's moldable
    packing wins when flushes are dense (many tasks per batch amortise
    its reconfiguration overhead), while a pinned all-1s fix-part
    partition wins at sparse arrival rates where FAR's reconfigurations
    dominate the short chains.  This meta-policy picks per batch —
    ``len(tasks) >= config.auto_dense_batch`` flushes through ``"far"``,
    anything sparser through ``"fix-part"`` — so a serving stream whose
    rate drifts across regimes gets the right planner at every flush
    without a config change.  The chosen name is recorded in
    ``extras["auto_choice"]``.
    """

    name = "auto-serve"

    def plan(
        self,
        tasks: Sequence[Task],
        spec: DeviceSpec,
        config: SchedulerConfig | None = None,
        tail: object | None = None,
    ) -> PlanResult:
        cfg = config or SchedulerConfig()
        choice = "far" if len(tasks) >= cfg.auto_dense_batch else "fix-part"
        res = get_policy(choice).plan(tasks, spec, cfg, tail)
        res.policy = self.name
        res.extras["auto_choice"] = choice
        return res


__all__ = [
    "SchedulerConfig",
    "PlanResult",
    "SchedulerPolicy",
    "BasePolicy",
    "LEGACY_KWARGS",
    "assignment_from_schedule",
    "register_policy",
    "get_policy",
    "available_policies",
]
