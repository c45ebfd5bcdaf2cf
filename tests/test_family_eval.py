"""Equivalence and unit tests for the phase-2 family evaluators.

The hard contract: ``evaluator="vectorized"`` and ``evaluator="sequential"``
pick the **bit-identical** winner — index, allocation, assignment,
pre-refine makespan, evaluated count and final schedule — on any workload,
spec, and prune setting.  These tests exercise it deterministically
(seeded random floats plus integer-duration workloads, which are dense in
exact time ties and therefore stress the ``(time, seq)`` tie-breaking);
the hypothesis suite in ``test_scheduler_property.py`` adds randomized
coverage.
"""

import numpy as np
import pytest

from repro.core.device_spec import A30, A100, H100, TPU_POD_256
from repro.core.family_eval import (
    AUTO_MIN_FAMILY,
    AUTO_MIN_TASKS,
    EVALUATORS,
    HAVE_JAX,
    get_evaluator,
    family_areas,
    resolve_evaluator,
)
from repro.core.far import schedule_batch
from repro.core.allocations import allocation_family_deltas
from repro.core.policy import SchedulerConfig
from repro.core.problem import Task
from repro.core.repartition import LPTGroups, size_sorted_orders
from repro.core.timing import chains_makespan, chains_makespan_batch

SPECS = {"A30": A30, "A100": A100, "H100": H100, "TPU": TPU_POD_256}


def make_tasks(n, spec, seed=0, integer=False):
    """Random monotone profiles; integer mode is dense in exact ties."""
    rng = np.random.default_rng(seed)
    tasks = []
    for i in range(n):
        t1 = float(rng.integers(1, 20)) if integer \
            else float(rng.uniform(0.5, 100.0))
        times, cur = {}, t1
        for s in spec.sizes:
            if s == min(spec.sizes):
                times[s] = cur
            else:
                shrink = float(rng.integers(1, 4)) / 4.0 if integer \
                    else float(rng.uniform(0.3, 1.0))
                cur = cur * shrink
                times[s] = cur
        tasks.append(Task(id=i, times=times))
    return tasks


def assert_identical(rs, rv):
    assert rs.winner_index == rv.winner_index
    assert rs.allocation == rv.allocation
    assert rs.makespan_before_refine == rv.makespan_before_refine
    assert rs.evaluated == rv.evaluated
    assert rs.assignment.node_tasks == rv.assignment.node_tasks
    assert rs.schedule.items == rv.schedule.items
    assert rs.schedule.reconfigs == rv.schedule.reconfigs


@pytest.mark.parametrize("spec_name", sorted(SPECS))
@pytest.mark.parametrize("n", [1, 2, 7, 24, 60])
@pytest.mark.parametrize("integer", [False, True])
def test_vectorized_matches_sequential(spec_name, n, integer):
    spec = SPECS[spec_name]
    tasks = make_tasks(n, spec, seed=n * 7 + integer, integer=integer)
    for prune in (True, False):
        rs = schedule_batch(tasks, spec, SchedulerConfig(
            evaluator="sequential", prune=prune, refine=False))
        rv = schedule_batch(tasks, spec, SchedulerConfig(
            evaluator="vectorized", prune=prune, refine=False))
        assert_identical(rs, rv)


@pytest.mark.parametrize("spec_name", ["A100", "TPU"])
def test_vectorized_matches_sequential_with_refine(spec_name):
    """End-to-end (phases 2+3): identical winner implies identical final
    schedule; run once to guard the full pipeline wiring."""
    spec = SPECS[spec_name]
    tasks = make_tasks(40, spec, seed=3)
    rs = schedule_batch(tasks, spec, SchedulerConfig(evaluator="sequential"))
    rv = schedule_batch(tasks, spec, SchedulerConfig(evaluator="vectorized"))
    assert rs.makespan == rv.makespan
    assert rs.schedule.items == rv.schedule.items
    assert rs.schedule.reconfigs == rv.schedule.reconfigs


def test_synth_workload_equivalence():
    """The benchmark workloads (paper §6.3 generators) stay bit-identical
    across evaluators — the t_cost acceptance surface in miniature."""
    from repro.core.synth import generate_tasks, workload

    cfg = workload("mixed", "wide", A100)
    tasks = generate_tasks(120, A100, cfg, seed=0)
    rs = schedule_batch(tasks, A100, SchedulerConfig(evaluator="sequential"))
    rv = schedule_batch(tasks, A100, SchedulerConfig(evaluator="vectorized"))
    assert_identical(rs, rv)
    assert rs.makespan == rv.makespan


def test_chains_makespan_batch_matches_scalar():
    """The batched phase-2 scorer is bit-identical per candidate to
    chains_makespan on the same duration chains."""
    spec = A100
    rng = np.random.default_rng(5)
    cands = []
    for seed in range(6):
        tasks = make_tasks(int(rng.integers(1, 30)), spec, seed=seed)
        first, _ = allocation_family_deltas(tasks, spec)
        groups = LPTGroups(tasks, first, spec)
        a, nd = groups.schedule_with_durs()
        cands.append((a.node_tasks, nd))
    N = len(spec.nodes)
    index = {node.key: i for i, node in enumerate(spec.nodes)}
    L = max(
        (len(v) for nt, _ in cands for v in nt.values()), default=1
    )
    cd = np.zeros((len(cands), N, L))
    cl = np.zeros((len(cands), N), dtype=np.int64)
    for c, (nt, nd) in enumerate(cands):
        for key, durs in nd.items():
            cd[c, index[key], :len(durs)] = durs
            cl[c, index[key]] = len(durs)
    batch = chains_makespan_batch(spec, cd, cl)
    for c, (nt, nd) in enumerate(cands):
        assert batch[c] == chains_makespan(spec, nt, nd)


def test_chains_makespan_batch_empty():
    assert chains_makespan_batch(
        A100, np.zeros((3, len(A100.nodes), 1)),
        np.zeros((3, len(A100.nodes)), dtype=np.int64),
    ).tolist() == [0.0, 0.0, 0.0]


def test_family_areas_match_stepwise_fold():
    """The accumulated area sequence equals the one-delta-at-a-time fold
    the sequential loop would produce (same IEEE operations)."""
    spec = A100
    tasks = make_tasks(30, spec, seed=11)
    first, deltas = allocation_family_deltas(tasks, spec)
    areas = family_areas(tasks, first, deltas)
    area = 0.0
    for t, s in zip(tasks, first):  # left fold, not sum()'s compensation
        area += s * t.times[s]
    alloc = list(first)
    assert areas[0] == area
    for k, (j, s_new) in enumerate(deltas):
        s_old = alloc[j]
        t = tasks[j]
        area = area + (s_new * t.times[s_new] - s_old * t.times[s_old])
        alloc[j] = s_new
        assert areas[k + 1] == area


def test_size_sorted_orders_layout():
    spec = A30
    tasks = make_tasks(12, spec, seed=2)
    orders = size_sorted_orders(tasks, spec)
    for k, s in enumerate(spec.sizes):
        ref = sorted(tasks, key=lambda t: (-t.times[s], t.id))
        assert orders.ids[k].tolist() == [t.id for t in ref]
        assert orders.durs[k].tolist() == [t.times[s] for t in ref]
        # inv is the inverse permutation of order
        assert (orders.order[k][orders.inv[k]] == np.arange(len(tasks))).all()


def test_config_validation():
    with pytest.raises(ValueError, match="evaluator"):
        SchedulerConfig(evaluator="nope")
    for name in ("sequential", "incremental", "parallel", "vectorized",
                 "auto"):
        assert SchedulerConfig(evaluator=name).evaluator == name


def test_get_evaluator_unknown():
    with pytest.raises(KeyError, match="unknown family evaluator"):
        get_evaluator("nope")
    assert set(EVALUATORS) >= {
        "sequential", "incremental", "parallel", "vectorized",
    }


def test_resolve_evaluator_dispatch():
    from repro.core import fastsim

    big_n = AUTO_MIN_TASKS
    big_f = AUTO_MIN_FAMILY
    auto = SchedulerConfig(evaluator="auto")
    if fastsim.available():
        expected = "incremental"
    elif HAVE_JAX:
        expected = "vectorized"
    else:
        expected = "sequential"
    assert resolve_evaluator(auto, big_n, big_f) == expected
    # small problems stay sequential under auto
    assert resolve_evaluator(auto, 8, 4) == "sequential"
    # config-overridable floor: a tiny floor admits the compiled tier on
    # small batches, a huge floor pushes auto back to sequential
    low = SchedulerConfig(evaluator="auto", evaluator_floor=8)
    if fastsim.available():
        assert resolve_evaluator(low, 8, big_f) == "incremental"
    high = SchedulerConfig(evaluator="auto", evaluator_floor=10**9)
    assert resolve_evaluator(high, big_n, big_f) == "sequential"
    # the replay reference path always scores sequentially
    for name in ("vectorized", "incremental", "parallel"):
        ref = SchedulerConfig(evaluator=name, use_engine=False)
        assert resolve_evaluator(ref, big_n, big_f) == "sequential"
    forced = SchedulerConfig(evaluator="vectorized")
    assert resolve_evaluator(forced, 1, 1) == "vectorized"


def test_empty_batch():
    res = schedule_batch([], A100, SchedulerConfig(evaluator="vectorized"))
    assert res.makespan == 0.0 and res.family_size == 1


# -- vectorized off the CPU backend -----------------------------------------
# A TPU's float64 is emulated, so off the CPU the vectorized evaluator
# checks every device score against the host.  These tests steer that path
# on the CPU by reporting another backend.


@pytest.fixture
def off_cpu(monkeypatch):
    import repro.core.family_eval as fe

    monkeypatch.setattr(fe, "_platform", lambda: "tpu")
    return fe


@pytest.mark.parametrize("prune", [True, False])
def test_vectorized_off_cpu_checks_and_matches(off_cpu, prune):
    tasks = make_tasks(40, A100, seed=9)
    rs = schedule_batch(tasks, A100, SchedulerConfig(
        evaluator="sequential", prune=prune, refine=False))
    rv = schedule_batch(tasks, A100, SchedulerConfig(
        evaluator="vectorized", prune=prune, refine=False))
    assert_identical(rs, rv)


def test_vectorized_off_cpu_refuses_a_device_difference(off_cpu,
                                                         monkeypatch):
    """One ulp of difference on the device is refused, naming the first
    candidate that differs; no winner is returned."""
    exact = off_cpu._chains_program

    def one_ulp_late(spec, C, L):
        walk = exact(spec, C, L)
        return lambda d, n: np.nextafter(np.asarray(walk(d, n)), np.inf)

    monkeypatch.setattr(off_cpu, "_chains_program", one_ulp_late)
    tasks = make_tasks(40, A100, seed=9)
    with pytest.raises(off_cpu.DeviceMismatchError,
                       match="on tpu: family candidate 0 scores"):
        schedule_batch(tasks, A100, SchedulerConfig(
            evaluator="vectorized", prune=False, refine=False))


def test_auto_never_picks_vectorized_off_cpu(off_cpu, monkeypatch):
    from repro.core import fastsim

    monkeypatch.setattr(fastsim, "available", lambda: False)
    auto = SchedulerConfig(evaluator="auto", prune=False)
    assert resolve_evaluator(auto, 10**6, 10**6) == "sequential"


def test_vectorized_without_jax_raises(monkeypatch):
    import repro.core.family_eval as fe

    monkeypatch.setattr(fe, "HAVE_JAX", False)
    with pytest.raises(RuntimeError, match="needs jax"):
        schedule_batch(make_tasks(10, A100), A100,
                       SchedulerConfig(evaluator="vectorized"))


# -- incremental delta-replay evaluator -------------------------------------


@pytest.mark.parametrize("spec_name", sorted(SPECS))
@pytest.mark.parametrize("n", [1, 2, 7, 24, 60])
@pytest.mark.parametrize("integer", [False, True])
def test_incremental_matches_sequential(spec_name, n, integer):
    """The delta-replay evaluator inherits the full bit-identity
    contract, including the tie-dense integer workloads that stress the
    snapshot/restore divergence rules at every rank."""
    spec = SPECS[spec_name]
    tasks = make_tasks(n, spec, seed=n * 7 + integer, integer=integer)
    for prune in (True, False):
        rs = schedule_batch(tasks, spec, SchedulerConfig(
            evaluator="sequential", prune=prune, refine=False))
        ri = schedule_batch(tasks, spec, SchedulerConfig(
            evaluator="incremental", prune=prune, refine=False))
        assert_identical(rs, ri)


@pytest.mark.parametrize("spec_name", ["A100", "TPU"])
def test_incremental_python_fallback_matches(spec_name):
    """Without a C compiler the evaluator resimulates in pure Python —
    identical winners, no compiled backend involved."""
    from repro.core import fastsim

    spec = SPECS[spec_name]
    tasks = make_tasks(24, spec, seed=5)
    saved = fastsim._LOADED
    fastsim._LOADED = None  # simulate a failed build for this process
    try:
        for prune in (True, False):
            rs = schedule_batch(tasks, spec, SchedulerConfig(
                evaluator="sequential", prune=prune, refine=False))
            ri = schedule_batch(tasks, spec, SchedulerConfig(
                evaluator="incremental", prune=prune, refine=False))
            assert_identical(rs, ri)
    finally:
        fastsim._LOADED = saved


def test_incremental_with_refine():
    spec = A100
    tasks = make_tasks(40, spec, seed=3)
    rs = schedule_batch(tasks, spec, SchedulerConfig(evaluator="sequential"))
    ri = schedule_batch(tasks, spec, SchedulerConfig(evaluator="incremental"))
    assert rs.makespan == ri.makespan
    assert rs.schedule.items == ri.schedule.items
    assert rs.schedule.reconfigs == ri.schedule.reconfigs


def test_incremental_single_candidate_family():
    """A family of one (no deltas) never arms a trigger."""
    spec = A100
    tasks = [Task(id=0, times={s: 10.0 / s for s in spec.sizes})]
    first, deltas = allocation_family_deltas(tasks, spec)
    sub = deltas[:0]
    cfg = SchedulerConfig(evaluator="incremental", refine=False)
    rs = EVALUATORS["sequential"].evaluate(tasks, spec, first, sub, cfg)
    ri = EVALUATORS["incremental"].evaluate(tasks, spec, first, sub, cfg)
    assert rs.makespan == ri.makespan
    assert rs.index == ri.index == 0
    assert rs.assignment.node_tasks == ri.assignment.node_tasks


def test_incremental_pruned_to_zero_window():
    """All-ties integer durations can prune every non-first candidate;
    the winner scan must still agree after the first score."""
    spec = A30
    tasks = [Task(id=i, times={s: 8.0 for s in spec.sizes})
             for i in range(6)]  # no speedup: wider is strictly worse area
    first, deltas = allocation_family_deltas(tasks, spec)
    cfg = SchedulerConfig(evaluator="incremental", prune=True, refine=False)
    rs = EVALUATORS["sequential"].evaluate(tasks, spec, first, deltas, cfg)
    ri = EVALUATORS["incremental"].evaluate(tasks, spec, first, deltas, cfg)
    assert rs.makespan == ri.makespan
    assert rs.index == ri.index
    assert rs.evaluated == ri.evaluated
    assert rs.assignment.node_tasks == ri.assignment.node_tasks


# -- parallel family sharding -----------------------------------------------


@pytest.mark.parametrize("spec_name", ["A100", "TPU"])
@pytest.mark.parametrize("prune", [True, False])
def test_parallel_matches_sequential(spec_name, prune):
    spec = SPECS[spec_name]
    tasks = make_tasks(40, spec, seed=11)
    rs = schedule_batch(tasks, spec, SchedulerConfig(
        evaluator="sequential", prune=prune, refine=False))
    rp = schedule_batch(tasks, spec, SchedulerConfig(
        evaluator="parallel", prune=prune, refine=False,
        parallel_workers=2))
    assert_identical(rs, rp)


def test_parallel_worker_count_invariance():
    """The deterministic reduce makes the winner independent of the
    worker count (chunk boundaries move, the ordered scan does not)."""
    spec = A100
    tasks = make_tasks(30, spec, seed=2)
    results = [
        schedule_batch(tasks, spec, SchedulerConfig(
            evaluator="parallel", refine=False, parallel_workers=w))
        for w in (1, 2, 3)
    ]
    for other in results[1:]:
        assert_identical(results[0], other)
