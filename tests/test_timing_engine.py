"""Replay-equivalence contract of the incremental timing engine.

``TimingEngine`` promises: after ANY sequence of moves/swaps/appends and
undos, every accessor returns exactly what a fresh ``replay()`` of the same
assignment would — for both ``include_reconfig`` settings, both directions,
and with/without seam carry-over state.  ``ReplayEngine`` is the reference
implementation of the same API; these tests drive both through identical
edit sequences and require *exact* (``==``, not EPS) agreement, plus
end-to-end agreement of the engine-backed refinement paths with the
replay-backed ones.
"""

import dataclasses
import random

import pytest

from repro.core.device_spec import A30, A100, TPU_POD_256, InstanceNode
from repro.core.far import schedule_batch
from repro.core.policy import SchedulerConfig
from repro.core.multibatch import MultiBatchScheduler, Tail, seam_refine
from repro.core.problem import validate_schedule
from repro.core.refine import refine_assignment
from repro.core.repartition import (
    LPTGroups,
    list_schedule_allocation,
    replay,
)
from repro.core.allocations import allocation_family
from repro.core.synth import generate_tasks, workload
from repro.core.timing import ReplayEngine, TimingEngine, left_fold

NO_REFINE = SchedulerConfig(refine=False)

SPECS = (A30, A100, TPU_POD_256)


def test_left_fold_is_not_compensated_sum():
    """Chains are folded left to right, as replay adds them; Python's
    ``sum()`` compensates float rounding and gives another last bit."""
    values = [0.1] * 10
    assert left_fold(0.0, values) == 0.9999999999999999
    assert sum(values) == 1.0
    assert left_fold(5.0, []) == 5.0


def _assert_engines_agree(eng: TimingEngine, ref: ReplayEngine):
    for flag in (True, False):
        assert eng.makespan(flag) == ref.makespan(flag)
        assert eng.slice_end_times(flag) == ref.slice_end_times(flag)
        assert eng.node_end_times(flag) == ref.node_end_times(flag)
        assert eng.begin_mass(flag) == ref.begin_mass(flag)
    sched_e, sched_r = eng.schedule(), ref.schedule()
    assert sched_e.items == sched_r.items
    assert sched_e.reconfigs == sched_r.reconfigs


def _random_edit(rng, eng, ref, spec):
    """Apply one random valid edit to both engines; returns False if none."""
    occupied = [k for k, v in eng.chains.items() if v]
    if not occupied:
        return False
    kind = rng.choice(["move", "move", "swap"])
    if kind == "move":
        src = rng.choice(occupied)
        tid = rng.choice(eng.chains[src])
        dst = rng.choice([n.key for n in spec.nodes if n.key != src])
        eng.apply_move(tid, dst=dst, src=src)
        ref.apply_move(tid, dst=dst, src=src)
    else:
        if len(occupied) < 2:
            return False
        ka, kb = rng.sample(occupied, 2)
        ta = rng.choice(eng.chains[ka])
        tb = rng.choice(eng.chains[kb])
        eng.apply_swap(ta, tb)
        ref.apply_swap(ta, tb)
    return True


def _seam_tail(spec, seed):
    mb = MultiBatchScheduler(spec, mode="trivial")
    mb.add_batch(
        generate_tasks(6, spec, workload("mixed", "wide", spec), seed=seed)
    )
    return mb.tail


@pytest.mark.parametrize("spec", SPECS)
@pytest.mark.parametrize("direction", ["forward", "reverse"])
@pytest.mark.parametrize("with_tail", [False, True])
def test_engine_matches_replay_under_random_edits(spec, direction, with_tail):
    rng = random.Random(1234 + spec.n_slices)
    tasks = generate_tasks(
        12, spec, workload("mixed", "wide", spec), seed=3, id_offset=100
    )
    fam = allocation_family(tasks, spec)
    assignment = list_schedule_allocation(tasks, fam[len(fam) // 2], spec)
    ctx = {}
    if with_tail:
        tail = _seam_tail(spec, seed=7)
        ctx = dict(release=tail.release, alive=tail.alive)
    eng = TimingEngine(assignment, direction=direction, **ctx)
    ref = ReplayEngine(assignment, direction=direction, **ctx)
    snapshot = {k: list(v) for k, v in eng.chains.items()}
    _assert_engines_agree(eng, ref)
    for _ in range(25):
        if not _random_edit(rng, eng, ref, spec):
            break
        _assert_engines_agree(eng, ref)
    # speculative use: undo everything, bit-identical initial state + timing
    eng.undo_all()
    ref.undo_all()
    assert {k: v for k, v in eng.chains.items() if v} == \
        {k: v for k, v in snapshot.items() if v}
    _assert_engines_agree(eng, ref)


def test_engine_undo_interleaved_with_evaluation():
    spec = A100
    tasks = generate_tasks(10, spec, workload("good", "wide", spec), seed=5)
    assignment = schedule_batch(tasks, spec, NO_REFINE).assignment
    eng = TimingEngine(assignment)
    rng = random.Random(99)
    before = {
        flag: (eng.makespan(flag), eng.slice_end_times(flag))
        for flag in (True, False)
    }
    for _ in range(10):
        ref = ReplayEngine(eng.export_assignment())
        n_edits = rng.randint(1, 3)
        done = 0
        for _ in range(n_edits):
            if _random_edit(rng, eng, ref, spec):
                done += 1
        _assert_engines_agree(eng, ref)
        for _ in range(done):
            eng.undo()
        for flag in (True, False):
            assert (eng.makespan(flag), eng.slice_end_times(flag)) \
                == before[flag]


def test_task_begin_end_matches_schedule():
    spec = A100
    tasks = generate_tasks(9, spec, workload("poor", "narrow", spec), seed=2)
    assignment = schedule_batch(tasks, spec, NO_REFINE).assignment
    for direction in ("forward", "reverse"):
        eng = TimingEngine(assignment, direction=direction)
        sched = replay(assignment, direction=direction)
        for it in sched.items:
            assert eng.task_begin_end(it.task.id) == (it.begin, it.end)


def test_lpt_groups_warm_start_matches_cold_sort():
    spec = A100
    tasks = generate_tasks(15, spec, workload("mixed", "wide", spec), seed=11)
    fam = allocation_family(tasks, spec)
    groups = LPTGroups(tasks, fam[0], spec)
    for idx, alloc in enumerate(fam):
        if idx:
            prev = fam[idx - 1]
            j = next(i for i in range(len(alloc)) if alloc[i] != prev[i])
            groups.move(tasks[j], prev[j], alloc[j])
        warm = groups.schedule()
        cold = list_schedule_allocation(tasks, alloc, spec)
        assert warm.node_tasks == cold.node_tasks


@pytest.mark.parametrize("spec", SPECS)
def test_refine_engine_path_equals_replay_path(spec):
    for scaling, times in (("mixed", "wide"), ("poor", "narrow"),
                           ("good", "wide")):
        for n in (10, 22):
            tasks = generate_tasks(
                n, spec, workload(scaling, times, spec), seed=n
            )
            base = schedule_batch(tasks, spec, NO_REFINE).assignment
            a_asgn, a_sched, a_stats = refine_assignment(base, use_engine=True)
            b_asgn, b_sched, b_stats = refine_assignment(base, use_engine=False)
            assert a_sched.makespan == b_sched.makespan
            assert a_asgn.node_tasks == b_asgn.node_tasks
            assert (a_stats.moves, a_stats.swaps, a_stats.iterations) == \
                (b_stats.moves, b_stats.swaps, b_stats.iterations)


def test_seam_refine_engine_path_equals_replay_path():
    spec = A100
    for seed in range(3):
        tail = _seam_tail(spec, seed)
        batch = generate_tasks(
            10, spec, workload("mixed", "wide", spec),
            seed=seed + 50, id_offset=500,
        )
        asgn = schedule_batch(batch, spec).assignment
        for direction in ("forward", "reverse"):
            a = seam_refine(asgn, tail, direction, use_engine=True)
            b = seam_refine(asgn, tail, direction, use_engine=False)
            assert a[1].makespan == b[1].makespan
            assert a[0].node_tasks == b[0].node_tasks
            assert a[2:] == b[2:]  # move/swap counts


def test_schedule_batch_paths_identical_on_t4_t9_workloads():
    """Acceptance: phase-3 + seam move/swap makespans identical between the
    incremental-engine pipeline and the replay-per-query pipeline on the
    benchmark workload family (t4-t9 use these generators)."""
    spec = A100
    for scaling, times in (("poor", "wide"), ("mixed", "wide"),
                           ("good", "wide"), ("mixed", "narrow")):
        cfg = workload(scaling, times, spec)
        for n in (10, 30):
            tasks = generate_tasks(n, spec, cfg, seed=n)
            a = schedule_batch(tasks, spec, SchedulerConfig(use_engine=True))
            b = schedule_batch(tasks, spec, SchedulerConfig(use_engine=False))
            assert a.makespan == b.makespan
            assert a.assignment.node_tasks == b.assignment.node_tasks
            validate_schedule(a.schedule, tasks)
        # multi-batch chain with seam move/swap (t9)
        me = MultiBatchScheduler(spec, mode="move_swap", use_engine=True)
        mr = MultiBatchScheduler(spec, mode="move_swap", use_engine=False)
        for s in range(3):
            b = generate_tasks(8, spec, cfg, seed=s, id_offset=10_000 * s)
            me.add_batch(b)
            mr.add_batch(b)
        assert me.makespan == mr.makespan
        assert me.tail.release == mr.tail.release


def test_empty_and_single_task_engine():
    spec = A100
    from repro.core.repartition import Assignment

    empty = Assignment(spec, {}, {})
    eng = TimingEngine(empty)
    assert eng.makespan() == 0.0
    assert eng.schedule().items == []
    t = generate_tasks(1, spec, workload("mixed", "wide", spec), seed=0)
    asgn = schedule_batch(t, spec).assignment
    _assert_engines_agree(TimingEngine(asgn), ReplayEngine(asgn))


# --- suffix retraction (serving re-planning pulls appends back) ------------

def _snapshot(eng):
    # empty chains are inactive (and undo of an append leaves one behind,
    # matching the engine's existing behavior) — compare modulo them
    return (
        {k: list(v) for k, v in eng.chains.items() if v},
        {k: list(v) for k, v in eng.durs.items() if v},
    )


@pytest.mark.parametrize("spec", SPECS)
@pytest.mark.parametrize("with_tail", [False, True])
def test_retract_inverts_append_bit_for_bit(spec, with_tail):
    tasks = generate_tasks(
        6, spec, workload("mixed", "wide", spec), seed=11, id_offset=300
    )
    ctx = {}
    if with_tail:
        tail = _seam_tail(spec, seed=4)
        ctx = dict(release=tail.release, alive=tail.alive)
    base = schedule_batch(tasks[:3], spec, NO_REFINE).assignment
    eng = TimingEngine(base, **ctx)
    ref = ReplayEngine(base, **ctx)
    before = _snapshot(eng)
    m0 = eng.makespan()
    node = spec.nodes[0]
    for t in tasks[3:]:
        eng.tasks[t.id] = t   # the tasks dict is shared with `ref`
        eng.apply_append(t.id, node.key)
        ref.apply_append(t.id, node.key)
    _assert_engines_agree(eng, ref)
    for t in reversed(tasks[3:]):
        eng.apply_retract(t.id)
        ref.apply_retract(t.id, node.key)
    _assert_engines_agree(eng, ref)
    assert _snapshot(eng) == before
    assert eng.makespan() == m0
    # undo() of a retraction restores the retracted task exactly
    eng.apply_append(tasks[3].id, node.key)
    mid = _snapshot(eng)
    eng.apply_retract(tasks[3].id)
    eng.undo()
    assert _snapshot(eng) == mid
    assert eng.task_node[tasks[3].id] == node.key


def test_retract_suffix_and_error_cases():
    spec = A100
    tasks = generate_tasks(
        4, spec, workload("mixed", "wide", spec), seed=2, id_offset=500
    )
    from repro.core.repartition import Assignment

    eng = TimingEngine(Assignment(spec, {t.id: t for t in tasks}, {}))
    key = spec.nodes[0].key
    for t in tasks:
        eng.apply_append(t.id, key)
    # only the chain tail may be retracted (no-preemption: retracting an
    # interior task would shift the started work behind it)
    with pytest.raises(ValueError, match="suffix"):
        eng.apply_retract(tasks[0].id)
    # suffix retraction pops newest-first and reports the order
    assert eng.retract_suffix(key, 2) == [tasks[3].id, tasks[2].id]
    assert eng.chains[key] == [tasks[0].id, tasks[1].id]
    with pytest.raises(ValueError, match="retract 5"):
        eng.retract_suffix(key, 5)
    eng.retract_suffix(key, 2)
    assert eng.chains[key] == []
    # empty chain: nothing to retract
    with pytest.raises(ValueError, match="suffix"):
        eng.apply_retract(tasks[0].id, key)
    # the whole episode unwinds to the empty assignment
    eng.undo_all()
    assert eng.chains[key] == []
    assert eng.makespan() == 0.0


def test_online_withdraw_not_started_uses_retraction():
    """OnlineScheduler.withdraw_not_started pulls exactly the placements
    beginning after t, and the surviving schedule re-times consistently
    (survivors may only move earlier, never before the cut)."""
    from repro.core.online import OnlineScheduler

    spec = A100
    tasks = generate_tasks(
        10, spec, workload("mixed", "wide", spec), seed=6, id_offset=700
    )
    sched = OnlineScheduler(spec)
    for t in tasks:
        sched.submit(t)
    cut = sched.makespan / 2
    # read current timings (submit-time placement stamps go stale: later
    # appends can reshuffle the reconfiguration sequence)
    old_begin = {it.task.id: it.begin for it in sched.schedule().items}
    started = {tid for tid, b in old_begin.items() if b <= cut + 1e-9}
    withdrawn = sched.withdraw_not_started(cut)
    kept = {p.task_id for p in sched.placements}
    assert kept | {t.id for t in withdrawn} == {t.id for t in tasks}
    # "started" is judged against the pre-withdrawal timings: exactly the
    # started set survives, everything else is pulled back
    assert kept == started
    validate_schedule(sched.schedule(), check_reconfig=True)
    for p in sched.placements:      # survivors only ever move earlier
        assert p.begin <= old_begin[p.task_id] + 1e-9


# --- batched phase-2 scorer edge cases -------------------------------------

#: a degenerate one-instance device: the repartitioning tree is a single
#: leaf, so the event walk reduces to create + fold — the smallest spec
#: the batched scorer must still get bit-exact
SINGLE = dataclasses.replace(
    A30,
    name="single",
    roots=(InstanceNode(0, 0, 1, 1),),
    sizes=(1,),
    t_create={1: 0.11},
    t_destroy={1: 0.10},
)


def _batch_arrays(spec, cands):
    """(C, N, L) duration tensor + (C, N) lengths from per-node dicts."""
    import numpy as np

    index = {node.key: i for i, node in enumerate(spec.nodes)}
    N = len(spec.nodes)
    L = max((len(v) for nd in cands for v in nd.values()), default=1)
    cd = np.zeros((len(cands), N, max(L, 1)))
    cl = np.zeros((len(cands), N), dtype=np.int64)
    for c, nd in enumerate(cands):
        for key, durs in nd.items():
            cd[c, index[key], :len(durs)] = durs
            cl[c, index[key]] = len(durs)
    return cd, cl


def test_chains_makespan_batch_single_node_device():
    from repro.core.timing import chains_makespan, chains_makespan_batch

    root = SINGLE.roots[0]
    cands = [
        {},                                   # empty candidate
        {root.key: [2.0]},                    # one task
        {root.key: [3.0, 2.0, 1.0]},          # a chain
        {root.key: [1.0] * 7},                # ties
    ]
    cd, cl = _batch_arrays(SINGLE, cands)
    batch = chains_makespan_batch(SINGLE, cd, cl)
    for c, nd in enumerate(cands):
        ids = {k: list(range(len(v))) for k, v in nd.items()}
        assert batch[c] == chains_makespan(SINGLE, ids, nd)
    assert batch[0] == 0.0
    assert batch[1] == SINGLE.t_create[1] + 2.0


def test_chains_makespan_batch_all_ties_integer_durations():
    """The EPS-ordered-winner regression class from PR 3: integer
    durations tied across every chain still score bit-identically to the
    sequential walk (same heap tie-breaking, same fold order)."""
    from repro.core.timing import chains_makespan, chains_makespan_batch

    spec = A100
    ones = [n.key for n in spec.nodes if n.size == 1]
    twos = [n.key for n in spec.nodes if n.size == 2]
    cands = [
        {k: [1.0, 1.0, 1.0] for k in ones},
        {k: [2.0, 2.0] for k in ones[:3]} | {k: [2.0] for k in twos},
        {k: [1.0] for k in ones} | {twos[0]: [1.0, 1.0]},
        {ones[0]: []},                        # all-empty row
    ]
    cd, cl = _batch_arrays(spec, cands)
    batch = chains_makespan_batch(spec, cd, cl)
    for c, nd in enumerate(cands):
        ids = {k: list(range(len(v))) for k, v in nd.items()}
        assert batch[c] == chains_makespan(spec, ids, nd)
    assert batch[3] == 0.0


def test_chains_makespan_batch_mixed_empty_and_padded_rows():
    """Zero-length rows beside fully-padded ones: the walk must ignore
    padding past chain_len and inactive nodes entirely."""
    import numpy as np

    from repro.core.timing import chains_makespan, chains_makespan_batch

    spec = A30
    key0 = spec.nodes[1].key  # a non-root node
    nd = {key0: [4.0, 3.0]}
    cd, cl = _batch_arrays(spec, [nd, {}])
    # poison every slot past chain_len: padding must never be read
    L = cd.shape[2]
    cd[np.arange(L)[None, None, :] >= cl[:, :, None]] = 77.0
    batch = chains_makespan_batch(spec, cd, cl)
    assert batch[0] == chains_makespan(
        spec, {key0: [0, 1]}, nd
    )
    assert batch[1] == 0.0


# --- property-based fuzz (runs only when hypothesis is installed) ----------
try:
    from hypothesis import given, settings
    import hypothesis.strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    from repro.core.problem import Task

    @st.composite
    def assignment_and_edits(draw):
        spec = {"A30": A30, "A100": A100, "TPU": TPU_POD_256}[
            draw(st.sampled_from(["A30", "A100", "TPU"]))
        ]
        n = draw(st.integers(1, 8))
        tasks = []
        for i in range(n):
            t1 = draw(st.floats(0.5, 100.0, allow_nan=False))
            times, cur = {}, t1
            for s in spec.sizes:
                if s != min(spec.sizes):
                    cur *= draw(st.floats(0.3, 1.0))
                times[s] = cur
            tasks.append(Task(id=i, times=times))
        fam = allocation_family(tasks, spec)
        alloc = fam[draw(st.integers(0, len(fam) - 1))]
        seed = draw(st.integers(0, 2**16))
        direction = draw(st.sampled_from(["forward", "reverse"]))
        return spec, tasks, alloc, seed, direction

    @settings(max_examples=30, deadline=None)
    @given(assignment_and_edits())
    def test_engine_equivalence_hypothesis(case):
        spec, tasks, alloc, seed, direction = case
        assignment = list_schedule_allocation(tasks, alloc, spec)
        eng = TimingEngine(assignment, direction=direction)
        ref = ReplayEngine(assignment, direction=direction)
        rng = random.Random(seed)
        _assert_engines_agree(eng, ref)
        for _ in range(8):
            if not _random_edit(rng, eng, ref, spec):
                break
            _assert_engines_agree(eng, ref)
        eng.undo_all()
        ref.undo_all()
        _assert_engines_agree(eng, ref)


# --- runtime-truth stretches (closed-loop corrections) ---------------------

def test_apply_stretch_retimes_successors_and_undoes_exactly():
    spec = A100
    tasks = generate_tasks(
        4, spec, workload("mixed", "wide", spec), seed=9, id_offset=700
    )
    from repro.core.repartition import Assignment

    eng = TimingEngine(Assignment(spec, {t.id: t for t in tasks}, {}))
    key = spec.nodes[0].key
    for t in tasks:
        eng.apply_append(t.id, key)
    before = _snapshot(eng)
    m0 = eng.makespan()
    first = tasks[0]
    planned = first.times[spec.nodes[0].size]
    eng.apply_stretch(first.id, planned * 3.0)
    # the whole chain behind the stretched task shifts by the delta
    assert eng.makespan() == pytest.approx(m0 + 2.0 * planned)
    sched = eng.schedule()
    stretched_item = next(it for it in sched.items if it.task.id == first.id)
    assert stretched_item.end_override is not None
    assert stretched_item.corrected
    assert stretched_item.duration == pytest.approx(3.0 * planned)
    # shrink on top of the stretch: latest truth wins
    eng.apply_stretch(first.id, planned * 0.5)
    assert eng.makespan() == pytest.approx(m0 - 0.5 * planned)
    # undo unwinds both corrections exactly
    eng.undo()
    assert eng.makespan() == pytest.approx(m0 + 2.0 * planned)
    eng.undo()
    assert _snapshot(eng) == before
    assert eng.makespan() == m0
    assert first.id not in eng.stretched
    sched2 = eng.schedule()
    assert all(it.end_override is None for it in sched2.items)


def test_apply_stretch_sticks_through_retract_undo():
    """A stretched task that is retracted and then restored by undo()
    keeps its corrected duration (the correction is state, not an edit
    on the restored placement)."""
    spec = A30
    tasks = generate_tasks(
        3, spec, workload("mixed", "wide", spec), seed=5, id_offset=720
    )
    from repro.core.repartition import Assignment

    eng = TimingEngine(Assignment(spec, {t.id: t for t in tasks}, {}))
    key = spec.nodes[0].key
    for t in tasks:
        eng.apply_append(t.id, key)
    last = tasks[-1]
    eng.apply_stretch(last.id, 42.0)
    m_stretched = eng.makespan()
    eng.apply_retract(last.id)
    eng.undo()  # restore the retracted placement
    assert eng.makespan() == m_stretched
    assert eng.stretched[last.id] == 42.0


def test_apply_stretch_validation_and_replay_refusal():
    spec = A100
    tasks = generate_tasks(
        2, spec, workload("mixed", "wide", spec), seed=1, id_offset=740
    )
    from repro.core.repartition import Assignment

    asgn = Assignment(spec, {t.id: t for t in tasks}, {})
    eng = TimingEngine(asgn)
    key = spec.nodes[0].key
    eng.apply_append(tasks[0].id, key)
    with pytest.raises(ValueError, match="positive"):
        eng.apply_stretch(tasks[0].id, 0.0)
    # the replay reference models profiled durations only; runtime
    # corrections are a TimingEngine capability
    ref = ReplayEngine(asgn)
    ref.apply_append(tasks[0].id, key)
    with pytest.raises(NotImplementedError):
        ref.apply_stretch(tasks[0].id, 5.0)


def test_apply_cancel_marks_record_failed_and_undoes_exactly():
    spec = A100
    tasks = generate_tasks(
        4, spec, workload("mixed", "wide", spec), seed=3, id_offset=745
    )
    from repro.core.repartition import Assignment

    eng = TimingEngine(Assignment(spec, {t.id: t for t in tasks}, {}))
    key = spec.nodes[0].key
    for t in tasks:
        eng.apply_append(t.id, key)
    before = _snapshot(eng)
    m0 = eng.makespan()
    loser = tasks[0]
    eng.apply_cancel(loser.id, 2.5)
    # the cancelled occupancy record is truncated: successors move up
    sched = eng.schedule()
    rec = next(it for it in sched.items if it.task.id == loser.id)
    assert rec.failed and rec.corrected
    assert rec.duration == pytest.approx(2.5)
    assert eng.makespan() < m0
    live = [it for it in sched.items if not it.failed]
    assert loser.id not in {it.task.id for it in live}
    # cancel on top of cancel: latest truncation wins, undo unwinds both
    eng.apply_cancel(loser.id, 1.25)
    assert next(
        it for it in eng.schedule().items if it.task.id == loser.id
    ).duration == pytest.approx(1.25)
    eng.undo()
    assert next(
        it for it in eng.schedule().items if it.task.id == loser.id
    ).duration == pytest.approx(2.5)
    assert loser.id in eng.cancelled  # first cancel still holds
    eng.undo()
    assert _snapshot(eng) == before
    assert eng.makespan() == m0
    assert loser.id not in eng.cancelled
    assert all(not it.failed for it in eng.schedule().items)


def test_apply_credit_shrinks_to_remainder_and_undoes_exactly():
    spec = A100
    tasks = generate_tasks(
        3, spec, workload("mixed", "wide", spec), seed=6, id_offset=750
    )
    from repro.core.repartition import Assignment

    eng = TimingEngine(Assignment(spec, {t.id: t for t in tasks}, {}))
    key = spec.nodes[0].key
    for t in tasks:
        eng.apply_append(t.id, key)
    before = _snapshot(eng)
    m0 = eng.makespan()
    first = tasks[0]
    planned = first.times[spec.nodes[0].size]
    eng.apply_credit(first.id, 0.25 * planned)
    # checkpoint credit shrinks the record to its remainder; the task
    # stays LIVE (unlike cancel) and the chain behind it moves up
    sched = eng.schedule()
    rec = next(it for it in sched.items if it.task.id == first.id)
    assert not rec.failed and rec.corrected
    assert rec.duration == pytest.approx(0.75 * planned)
    assert eng.makespan() == pytest.approx(m0 - 0.25 * planned)
    eng.undo()
    assert _snapshot(eng) == before
    assert eng.makespan() == m0
    assert first.id not in eng.stretched


def test_apply_cancel_credit_validation_and_replay_refusal():
    spec = A100
    tasks = generate_tasks(
        2, spec, workload("mixed", "wide", spec), seed=2, id_offset=755
    )
    from repro.core.repartition import Assignment

    asgn = Assignment(spec, {t.id: t for t in tasks}, {})
    eng = TimingEngine(asgn)
    key = spec.nodes[0].key
    eng.apply_append(tasks[0].id, key)
    with pytest.raises(ValueError, match="positive"):
        eng.apply_cancel(tasks[0].id, 0.0)
    with pytest.raises(ValueError, match="positive"):
        eng.apply_credit(tasks[0].id, -1.0)
    # credit must leave a positive remainder: crediting the whole
    # duration (or more) would erase the placement instead of shrinking
    planned = tasks[0].times[spec.nodes[0].size]
    with pytest.raises(ValueError, match="remainder"):
        eng.apply_credit(tasks[0].id, planned)
    ref = ReplayEngine(asgn)
    ref.apply_append(tasks[0].id, key)
    with pytest.raises(NotImplementedError):
        ref.apply_cancel(tasks[0].id, 5.0)
    with pytest.raises(NotImplementedError):
        ref.apply_credit(tasks[0].id, 5.0)


# --- identity-cache safety + opcode-exhaustive undo ------------------------

@pytest.mark.parametrize("spec", SPECS)
def test_two_engines_same_spec_bit_identical(spec):
    """The observable half of the IdentityCache safety argument
    (timing.py): whether a derived-structure lookup hits or misses the
    identity-keyed cache, two engines built from the same spec and
    assignment produce bit-identical schedules — identity only gates
    recomputation, never the computed bytes."""
    import copy

    import numpy as np

    from repro.core.timing import _batch_spec_arrays

    tasks = generate_tasks(
        12, spec, workload("mixed", "wide", spec), seed=21, id_offset=760
    )
    fam = allocation_family(tasks, spec)
    assignment = list_schedule_allocation(tasks, fam[len(fam) // 2], spec)
    a = TimingEngine(assignment)
    b = TimingEngine(assignment)
    for flag in (True, False):
        assert a.makespan(flag) == b.makespan(flag)
        assert a.slice_end_times(flag) == b.slice_end_times(flag)
        assert a.node_end_times(flag) == b.node_end_times(flag)
    sa, sb = a.schedule(), b.schedule()
    assert sa.items == sb.items
    assert sa.reconfigs == sb.reconfigs
    # identical edit sequences stay bit-identical
    occupied = sorted(k for k, v in a.chains.items() if v)
    tid = a.chains[occupied[0]][0]
    dst = next(n.key for n in spec.nodes if n.key != occupied[0])
    for eng in (a, b):
        eng.apply_move(tid, dst=dst, src=occupied[0])
    assert a.makespan() == b.makespan()
    assert a.schedule().items == b.schedule().items
    # cache hit/miss parity, pinned directly: the second call for the
    # same anchor is a hit (the same tuple object); a deep copy of the
    # spec is a distinct anchor (forced miss) yet derives equal arrays
    first = _batch_spec_arrays(spec)
    assert _batch_spec_arrays(spec) is first
    fresh = _batch_spec_arrays(copy.deepcopy(spec))
    assert fresh is not first
    assert len(fresh) == len(first)
    for got, want in zip(fresh, first):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_undo_round_trip_covers_every_opcode():
    """Exhaustive apply_*/undo round trip, with the opcode set enumerated
    from the engine itself: every `kind == "..."` branch in undo() must
    be exercised by some driver below, and every apply_* method must have
    a driver.  A future opcode added without extending this test fails
    here, not in a confusing downstream search."""
    import ast as astmod
    import inspect
    import textwrap

    # opcodes undo() knows how to revert, read from its source
    undo_src = textwrap.dedent(inspect.getsource(TimingEngine.undo))
    undo_ops = {
        comp.value
        for node in astmod.walk(astmod.parse(undo_src))
        if isinstance(node, astmod.Compare)
        and isinstance(node.left, astmod.Name) and node.left.id == "kind"
        for comp in node.comparators
        if isinstance(comp, astmod.Constant) and isinstance(comp.value, str)
    }
    apply_ops = {
        name[len("apply_"):]
        for name in dir(TimingEngine) if name.startswith("apply_")
    }
    assert apply_ops == undo_ops, (
        "apply_* methods and undo() branches disagree — add the missing "
        "undo branch (or remove the dead one)"
    )

    spec = A100
    tasks = generate_tasks(
        10, spec, workload("mixed", "wide", spec), seed=11, id_offset=780
    )
    fam = allocation_family(tasks, spec)
    assignment = list_schedule_allocation(tasks, fam[0], spec)
    eng = TimingEngine(assignment)
    before = _snapshot(eng)
    before_stretched = dict(eng.stretched)
    before_times = {
        flag: (eng.makespan(flag), eng.slice_end_times(flag))
        for flag in (True, False)
    }
    before_sched = eng.schedule()

    def occupied():
        return sorted(k for k, v in eng.chains.items() if v)

    def spare():
        occ = set(occupied())
        return next(n.key for n in spec.nodes if n.key not in occ)

    def drive_move():
        src = occupied()[0]
        tid = eng.chains[src][0]
        eng.apply_move(tid, dst=spare(), src=src)

    def drive_swap():
        occ = occupied()
        if len(occ) < 2:  # single-chain layout cannot swap
            pytest.skip("allocation placed every task on one node")
        ka, kb = occ[0], occ[-1]
        eng.apply_swap(eng.chains[ka][0], eng.chains[kb][0])

    def drive_append():
        key = occupied()[0]
        tid = eng.chains[key][-1]
        eng.apply_extract(tid)
        eng.apply_append(tid, spare())

    def drive_extract_place():
        key = occupied()[0]
        tid = eng.chains[key][0]
        eng.apply_extract(tid)
        eng.apply_place(tid, spare())

    def drive_retract():
        key = occupied()[0]
        eng.apply_retract(eng.chains[key][-1], key)

    def drive_stretch():
        key = occupied()[0]
        eng.apply_stretch(eng.chains[key][0], 123.456)

    def drive_cancel():
        key = occupied()[0]
        eng.apply_cancel(eng.chains[key][0], 7.875)

    def drive_credit():
        key = occupied()[-1]
        tid = eng.chains[key][-1]
        begin, end = eng.task_begin_end(tid)
        eng.apply_credit(tid, (end - begin) * 0.5)

    drivers = {
        "move": drive_move,
        "swap": drive_swap,
        "append": drive_append,
        "extract": drive_extract_place,
        "place": drive_extract_place,
        "retract": drive_retract,
        "stretch": drive_stretch,
        "cancel": drive_cancel,
        "credit": drive_credit,
    }
    assert set(drivers) == apply_ops, (
        "a new apply_* opcode has no driver here — extend the round trip"
    )
    for op in sorted(drivers):
        drivers[op]()
    logged = {entry[0] for entry in eng._log}
    assert logged == undo_ops, (
        f"drivers exercised {sorted(logged)} but undo() handles "
        f"{sorted(undo_ops)}"
    )
    eng.undo_all()
    assert _snapshot(eng) == before
    assert dict(eng.stretched) == before_stretched
    after_times = {
        flag: (eng.makespan(flag), eng.slice_end_times(flag))
        for flag in (True, False)
    }
    assert after_times == before_times
    after_sched = eng.schedule()
    assert after_sched.items == before_sched.items
    assert after_sched.reconfigs == before_sched.reconfigs
