"""FAR — Family of Allocations and Repartitioning (paper §3).

``schedule_batch`` runs the three phases:

  1. generate the Turek allocation family (``allocations``);
  2. schedule every allocation with Algorithm 1 (``repartition``) and keep
     the one with the smallest makespan;
  3. refine the winner with task moves/swaps (``refine``).

An admissible pruning accelerates phase 2: along the family the per-task
work is non-decreasing (each step re-minimises over strictly larger sizes)
while ``h_max`` is non-increasing, so once ``area / #slices`` alone reaches
the incumbent makespan every later allocation is dominated and the loop can
stop.  This never changes the selected schedule, only skips provably-worse
candidates.

All knobs live in :class:`~repro.core.policy.SchedulerConfig`;
``schedule_batch(tasks, spec, config=...)`` is the direct entry point and
``get_policy("far").plan(...)`` the registry one.  The legacy boolean
kwargs (``refine=``/``prune=``/``deep_refine=``/``use_engine=``) still
work through a deprecation shim that names the config field to use.
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Sequence

from repro.core.allocations import Allocation, allocation_family_deltas
from repro.core.device_spec import DeviceSpec
from repro.core.family_eval import get_evaluator, resolve_evaluator
from repro.core.policy import (
    LEGACY_KWARGS,
    BasePolicy,
    PlanResult,
    SchedulerConfig,
    register_policy,
)
from repro.core.problem import Schedule, Task, area_lower_bound, bind_tasks
from repro.core.refine import RefineStats, refine_assignment
from repro.core.repartition import Assignment, replay
from repro.core.spans import span


@dataclasses.dataclass
class FARResult:
    schedule: Schedule
    assignment: Assignment
    allocation: Allocation
    family_size: int
    evaluated: int              # allocations actually scheduled (post-pruning)
    winner_index: int
    refine_stats: RefineStats | None
    makespan_before_refine: float
    elapsed_s: float
    phase_s: dict | None = None  # wall time per phase (family/evaluate/refine)

    @property
    def makespan(self) -> float:
        return self.schedule.makespan


def schedule_batch(
    tasks: Sequence[Task],
    spec: DeviceSpec,
    config: SchedulerConfig | None = None,
    **legacy,
) -> FARResult:
    """Run FAR on one batch of tasks (back-compat wrapper).

    Builds a :class:`SchedulerConfig` from the legacy boolean kwargs (each
    emits a :class:`DeprecationWarning` naming the config field to use)
    and delegates to the config-driven implementation.
    """
    if config is not None and not isinstance(config, SchedulerConfig):
        # the pre-config signature took refine positionally third; reject
        # loudly instead of silently binding a boolean to `config`
        raise TypeError(
            f"schedule_batch() third argument must be a SchedulerConfig, "
            f"got {type(config).__name__}; legacy positional booleans "
            f"moved to SchedulerConfig fields (e.g. SchedulerConfig("
            f"refine=...))"
        )
    if legacy:
        changes = {}
        for name, value in legacy.items():
            field = LEGACY_KWARGS.get(name)
            if field is None:
                raise TypeError(
                    f"schedule_batch() got an unexpected keyword argument "
                    f"{name!r}"
                )
            warnings.warn(
                f"schedule_batch({name}=...) is deprecated; pass "
                f"config=SchedulerConfig({field}=...) instead",
                DeprecationWarning,
                stacklevel=2,
            )
            changes[field] = value
        config = (config or SchedulerConfig()).replace(**changes)
    return far_schedule(tasks, spec, config or SchedulerConfig())


def far_schedule(
    tasks: Sequence[Task],
    spec: DeviceSpec,
    config: SchedulerConfig,
) -> FARResult:
    """The three FAR phases, driven entirely by ``config``.

    ``config.deep_refine`` (beyond-paper) follows phase 3 with an
    exact-evaluation greedy move/swap search (the §4.3 seam engine against
    an empty tail): each candidate edit is scored exactly, so it
    monotonically improves and tends to pick up the last few percent on
    small batches where the paper's margin heuristics run out.

    ``config.use_engine`` selects the incremental timing path (warm-started
    family evaluation + engine-scored refinement, default) or the cold
    replay-per-candidate reference path.  Both produce identical schedules;
    the flag exists for the equivalence tests and perf baselines.

    ``config.evaluator`` selects the phase-2 family evaluator —
    ``"sequential"``, ``"vectorized"`` (chunked array-program scoring) or
    ``"auto"`` — all bit-identical in output; see
    :mod:`repro.core.family_eval`."""
    eps = config.eps
    t0 = time.perf_counter()
    with span("repro.plan.family"):
        if not tasks:
            empty = Assignment(spec, {}, {})
            return FARResult(
                replay(empty), empty, (), 1, 0, 0, None, 0.0,
                time.perf_counter() - t0,
            )
        # heterogeneous profiles are lowered onto this device's kind here;
        # size-keyed tasks pass through untouched (the back-compat shim)
        tasks = bind_tasks(tasks, spec)
        sizes_needed = set(spec.sizes)
        for task in tasks:
            if not sizes_needed <= task.times.keys():
                missing = [s for s in spec.sizes if s not in task.times]
                raise ValueError(
                    f"task {task.id} lacks times for sizes {missing} "
                    f"on {spec.name}"
                )

        first, deltas = allocation_family_deltas(tasks, spec)
        family_size = len(deltas) + 1
    t1 = time.perf_counter()

    with span("repro.plan.evaluate"):
        # Phase 2: score the family through the configured evaluator
        # (family_eval.py).  "sequential" warm-starts per-size LPT groups
        # across the one-task deltas and scores each candidate with the
        # lean chains_makespan; "vectorized" lowers the same simulation
        # into a chunked array program; both select the identical
        # EPS-ordered winner and only the winner is ever replayed into a
        # Schedule.
        evaluator = get_evaluator(
            resolve_evaluator(config, len(tasks), family_size)
        )
        winner = evaluator.evaluate(tasks, spec, first, deltas, config)
        makespan_p2 = winner.makespan
        win_idx = winner.index
        assignment = winner.assignment
        winner_alloc = winner.allocation
        evaluated = winner.evaluated
    t2 = time.perf_counter()

    with span("repro.plan.refine"):
        stats: RefineStats | None = None
        schedule: Schedule
        if config.refine:
            # the winner's un-refined Schedule is never consumed when
            # phase 3 runs (it re-derives the final one), so skip that
            # replay entirely
            assignment, schedule, stats = refine_assignment(
                assignment, max_iterations=config.max_refine_iterations,
                use_engine=config.use_engine,
            )
        else:
            schedule = replay(assignment)
        if config.deep_refine:
            from repro.core.multibatch import Tail, seam_refine

            assignment2, schedule2, mv, sw = seam_refine(
                assignment, Tail.empty(spec), "forward",
                use_engine=config.use_engine,
            )
            if schedule2.makespan < schedule.makespan - eps:
                assignment, schedule = assignment2, schedule2
                if stats is not None:
                    stats.moves += mv
                    stats.swaps += sw
    t3 = time.perf_counter()

    return FARResult(
        schedule=schedule,
        assignment=assignment,
        allocation=winner_alloc,
        family_size=family_size,
        evaluated=evaluated,
        winner_index=win_idx,
        refine_stats=stats,
        makespan_before_refine=makespan_p2,
        elapsed_s=time.perf_counter() - t0,
        phase_s={"family": t1 - t0, "evaluate": t2 - t1, "refine": t3 - t2},
    )


@register_policy("far")
class FARPolicy(BasePolicy):
    """The paper's FAR scheduler as a registry policy."""

    def _plan_fresh(
        self, tasks: Sequence[Task], spec: DeviceSpec, config: SchedulerConfig
    ) -> PlanResult:
        far = far_schedule(tasks, spec, config)
        return PlanResult(
            policy=self.name,
            schedule=far.schedule,
            makespan=far.makespan,
            assignment=far.assignment,
            elapsed_s=far.elapsed_s,
            phase_s=far.phase_s,
            extras={"far": far},
        )


def rho(result: FARResult | PlanResult, tasks: Sequence[Task]) -> float:
    """Paper §6.4 error-vs-optimum proxy: makespan / area lower bound."""
    return result.makespan / area_lower_bound(tasks, result.schedule.spec)
