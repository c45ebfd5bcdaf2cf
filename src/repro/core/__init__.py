"""FAR — the paper's contribution: moldable task scheduling with dynamic
repartitioning for MIG-style reconfigurable accelerators."""

from repro.core.allocations import allocation_family, first_allocation
from repro.core.device_spec import (
    A30,
    A100,
    H100,
    SPECS,
    TPU_POD_256,
    TPU_SUPERPOD_512,
    V5E_1,
    V5E_2X2,
    DeviceSpec,
    InstanceNode,
    multi_gpu,
)
from repro.core.family_eval import (
    FamilyEvaluator,
    get_evaluator,
    register_evaluator,
)
from repro.core.far import FARResult, far_schedule, rho, schedule_batch
from repro.core.cluster import (
    ClusterMultiBatchScheduler,
    ClusterPlan,
    ClusterSchedule,
    ClusterSpec,
    cluster,
    partition_batch,
    validate_cluster_schedule,
)
from repro.core.multibatch import (
    ConcatResult,
    MultiBatchScheduler,
    Tail,
    concatenate,
    multibatch_baseline,
    tail_after,
)
from repro.core.online import OnlinePlacement, OnlineScheduler
from repro.core.policy import (
    PlanResult,
    SchedulerConfig,
    SchedulerPolicy,
    available_policies,
    get_policy,
    register_policy,
)
from repro.core.faults import (
    ExecutionDraw,
    FaultInjector,
    FaultRunReport,
    FaultSpec,
    ProfileCalibration,
    RetryPolicy,
    SpeculationPolicy,
    demote_shrink,
    execute_open_loop,
    run_with_faults,
)
from repro.core.service import (
    CheckpointEvent,
    CorrectionEvent,
    Decision,
    OutageEvent,
    ReplanEvent,
    RetryEvent,
    SchedulingService,
    ServiceStats,
    SpeculationEvent,
)
from repro.core.problem import (
    InfeasibleScheduleError,
    Profile,
    ProfileCoverageError,
    ReconfigEvent,
    Schedule,
    ScheduledTask,
    Task,
    area_lower_bound,
    bind_tasks,
    lower_bound,
    remainder_task,
    transfer_profile,
    validate_schedule,
)
from repro.core.refine import RefineStats, refine_assignment
from repro.core.sharded import (
    FastDecision,
    ScaleStats,
    ShardedSchedulingService,
)
from repro.core.traces import (
    TraceEvent,
    TraceSpec,
    trace_digest,
    trace_events,
)
from repro.core.repartition import (
    Assignment,
    LPTGroups,
    alive_at_end,
    list_schedule_allocation,
    list_schedule_groups,
    replay,
)
from repro.core.timing import ReplayEngine, TimingEngine, make_engine

__all__ = [
    "A30", "A100", "H100", "SPECS", "TPU_POD_256", "TPU_SUPERPOD_512",
    "V5E_1", "V5E_2X2",
    "DeviceSpec", "InstanceNode", "multi_gpu",
    "Task", "Profile", "bind_tasks", "remainder_task", "transfer_profile",
    "Schedule", "ScheduledTask",
    "ReconfigEvent", "InfeasibleScheduleError", "ProfileCoverageError",
    "validate_schedule",
    "area_lower_bound", "lower_bound",
    "ClusterSpec", "ClusterSchedule", "ClusterPlan", "cluster",
    "ClusterMultiBatchScheduler", "partition_batch",
    "validate_cluster_schedule",
    "allocation_family", "first_allocation",
    "Assignment", "list_schedule_allocation", "list_schedule_groups",
    "LPTGroups", "replay", "alive_at_end",
    "TimingEngine", "ReplayEngine", "make_engine",
    "RefineStats", "refine_assignment",
    "FARResult", "far_schedule", "schedule_batch", "rho",
    "FamilyEvaluator", "get_evaluator", "register_evaluator",
    "MultiBatchScheduler", "Tail", "ConcatResult", "concatenate",
    "multibatch_baseline", "tail_after",
    "OnlineScheduler", "OnlinePlacement",
    "SchedulerConfig", "SchedulerPolicy", "PlanResult",
    "register_policy", "get_policy", "available_policies",
    "SchedulingService", "ServiceStats", "Decision", "ReplanEvent",
    "CorrectionEvent", "RetryEvent", "OutageEvent",
    "SpeculationEvent", "CheckpointEvent",
    "RetryPolicy", "FaultSpec", "FaultInjector", "FaultRunReport",
    "ExecutionDraw", "demote_shrink", "run_with_faults",
    "execute_open_loop",
    "SpeculationPolicy", "ProfileCalibration",
    "ShardedSchedulingService", "ScaleStats", "FastDecision",
    "TraceSpec", "TraceEvent", "trace_events", "trace_digest",
]
