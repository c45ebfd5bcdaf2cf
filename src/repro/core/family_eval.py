"""FAR phase-2 family evaluation behind a pluggable evaluator.

Phase 2 scores every Turek-family candidate with Algorithm 1 and keeps the
EPS-ordered winner (ties broken by family index).  This module owns that
loop behind a small registry so the scoring engine is swappable through
``SchedulerConfig(evaluator=...)`` while the *selection semantics* stay in
exactly one place (:func:`_winner_scan`):

* ``"sequential"`` — the reference path: one warm-started
  :class:`~repro.core.repartition.LPTGroups` simulation per candidate
  (or cold ``list_schedule_allocation`` + ``replay`` when
  ``config.use_engine`` is off).  The admissible prune area is maintained
  incrementally from the one-task family deltas (O(1) per candidate)
  instead of re-summing all tasks each iteration.
* ``"incremental"`` — delta-replay scoring: consecutive family candidates
  differ by one task's allocation, so each simulation snapshots its state
  right before the next delta's divergence point (derived from the LPT
  ranks the moved task leaves and enters) and the next candidate replays
  only the suffix.  The post-divergence resimulation runs in a small
  compiled C replica of Algorithm 1's heap loop
  (:mod:`repro.core.fastsim`, built on demand with the system compiler,
  strict IEEE flags); without a compiler a pure-Python full resimulation
  per candidate keeps the results identical.
* ``"parallel"`` — family sharding across a ``concurrent.futures``
  process pool: workers score contiguous candidate chunks with the
  sequential pipeline, the parent reduces the ordered scores through
  :func:`_winner_scan`, so selection (prune break, EPS rule, tie-break,
  ``evaluated``) is bit-identical and independent of worker count or
  completion order.
* ``"vectorized"`` — an array program that scores *chunks of candidates at
  once*.  Algorithm 1's heap is replaced by a ``(chunk, nodes)`` tensor
  lockstep: the device tree is tiny and fixed, so the event queue holds at
  most one entry per tree node and the pop becomes a masked argmin over
  the node axis, identical across all candidates of the chunk.  The
  per-size LPT groups come from one set of
  :func:`~repro.core.repartition.size_sorted_orders` total orders —
  consecutive candidates differ in exactly one task
  (``allocation_family_deltas``), so a chunk is a boolean membership
  tensor over those fixed orders, built by two column flips per candidate.
  The simulation is a jax-jitted ``lax.scan`` in float64 and the
  resulting per-node duration chains are scored by a jitted event walk
  (the batched form of :func:`~repro.core.timing.chains_makespan`); both
  run on jax's default device, compiled once per shape bucket and
  cached.  It needs jax, and raises without it.  Off the CPU backend
  each chunk is checked against the host, and a device whose float64
  is not IEEE raises :class:`DeviceMismatchError` at the first
  candidate it scores differently.
* ``"auto"`` — three-way dispatch: ``"incremental"`` when the C backend
  is buildable and the batch clears ``AUTO_MIN_TASKS_INCREMENTAL``,
  else ``"vectorized"`` when jax is importable, computes on the CPU and
  the batch/family are large enough to amortize the array program
  (``AUTO_MIN_TASKS`` pruned / ``AUTO_MIN_TASKS_UNPRUNED`` full-family,
  with ``AUTO_MIN_FAMILY``), else ``"sequential"``.
  ``SchedulerConfig(evaluator_floor=)`` overrides the task floors.

**Equivalence contract:** every evaluator returns a bit-identical winner —
index, allocation, assignment and makespan — for any workload and spec.
The vectorized path earns this by construction rather than by tolerance:
every floating-point accumulation (chain folds, the serialized
reconfiguration tail, the prune-area recurrence) performs the same IEEE
operations in the same order as the sequential code, the lockstep pop
reproduces the heap's ``(time, seq)`` tie-breaking exactly, and the final
winner/prune scan is the shared :func:`_winner_scan` driver.  Enforced by
``tests/test_family_eval.py`` and the hypothesis property suite.
"""

from __future__ import annotations

import bisect
import dataclasses
import heapq
import os
from typing import Callable, Sequence

import numpy as np

from repro.core.allocations import Allocation
from repro.core.device_spec import DeviceSpec
from repro.core.problem import Task
from repro.core.repartition import (
    Assignment,
    LPTGroups,
    list_schedule_allocation,
    replay,
    size_sorted_orders,
)
from repro.core.timing import (
    IdentityCache,
    _batch_spec_arrays,
    chains_makespan,
    left_fold,
)

# jax is probed, not imported: `import repro.core` must stay free of
# jax's multi-second import / backend init for users on the sequential
# path.  The modules load lazily on first vectorized evaluation.
import importlib.util

HAVE_JAX = importlib.util.find_spec("jax") is not None


def _jax_modules():
    """(jax, jax.numpy), imported on first use."""
    import jax
    import jax.numpy as jnp

    return jax, jnp


def _platform() -> str:
    """jax's default backend.  Only ``"cpu"`` is known to compute float64
    in IEEE arithmetic (the TPU emulates it with pairs of float32)."""
    jax, _ = _jax_modules()
    return jax.default_backend()

#: "auto" dispatch thresholds, calibrated on the container benchmarks
#: (benchmarks/t_cost.py, paired medians).  The incremental evaluator's
#: compiled delta-replay wins as soon as candidates are expensive enough
#: to amortise its buffer setup (n>=256 with the usual prune window;
#: measured ~2.2x at n=500 pruned, ~4x at n=1000, ~5.7x at n=2000, and
#: up to ~8x full-family), so it is auto's first choice whenever the C
#: backend is buildable.  The
#: vectorized array program is the fallback tier (jax present, no C
#: compiler): its per-step cost is fixed per chunk while the sequential
#: cost is per *scored* candidate, so it wins where many candidates are
#: actually scored — unpruned (full-family) runs from moderate sizes on
#: (1.2-1.6x at n=500-2000 on the 2-vCPU CI box), pruned runs only once
#: the batch is so large that the ~2-dozen-candidate prune window still
#: beats the scan's fixed dispatch floor (crossover n~2000; margin
#: added).  ``SchedulerConfig(evaluator_floor=)`` overrides the task
#: floors without touching these module constants.
AUTO_MIN_TASKS_INCREMENTAL = 256  # delta-replay: wins from small batches
AUTO_MIN_TASKS = 3072          # pruned runs: scored window stays ~20-30
AUTO_MIN_TASKS_UNPRUNED = 512  # full-family runs: every candidate scored
AUTO_MIN_FAMILY = 48

#: chunk sizes for the vectorized scan.  Every chunk pays a full scan
#: pass, so a pruned run starts with one prune-window-sized chunk (the
#: admissible prune usually stops within a few dozen candidates) and an
#: unpruned run scores the whole family in one pass (memory-capped).
MAX_CHUNK = 32
MAX_FAMILY_CHUNK = 512


@dataclasses.dataclass
class FamilyWinner:
    """Phase-2 outcome: the EPS-ordered family winner."""

    makespan: float
    index: int
    assignment: Assignment
    allocation: Allocation
    evaluated: int


# -- registry ---------------------------------------------------------------

EVALUATORS: dict[str, "FamilyEvaluator"] = {}


def register_evaluator(name: str):
    """Class decorator adding a family evaluator under ``name``."""

    def deco(cls):
        cls.name = name
        EVALUATORS[name] = cls()
        return cls

    return deco


def get_evaluator(name: str) -> "FamilyEvaluator":
    try:
        return EVALUATORS[name]
    except KeyError:
        raise KeyError(
            f"unknown family evaluator {name!r}; "
            f"available: {', '.join(sorted(EVALUATORS))}"
        ) from None


def resolve_evaluator(config, n_tasks: int, family_size: int) -> str:
    """Map ``config.evaluator`` to a concrete evaluator name.

    The replay reference path (``use_engine=False``) always scores
    sequentially — it exists to cross-check the engine pipeline, so it
    must stay on the unoptimised code path.
    """
    name = config.evaluator
    if name == "auto":
        if not config.use_engine or family_size < AUTO_MIN_FAMILY:
            return "sequential"
        floor = getattr(config, "evaluator_floor", None)
        floor_inc = AUTO_MIN_TASKS_INCREMENTAL if floor is None else floor
        if floor is None:
            floor_vec = (
                AUTO_MIN_TASKS if config.prune else AUTO_MIN_TASKS_UNPRUNED
            )
        else:
            floor_vec = floor
        if n_tasks >= floor_inc:
            from repro.core import fastsim

            if fastsim.available():
                return "incremental"
        if HAVE_JAX and n_tasks >= floor_vec and _platform() == "cpu":
            return "vectorized"
        return "sequential"
    if name in ("vectorized", "incremental", "parallel") \
            and not config.use_engine:
        return "sequential"
    return name


# -- shared selection semantics ---------------------------------------------


def family_areas(
    tasks: Sequence[Task], first: Allocation, deltas: list[tuple[int, int]]
) -> np.ndarray:
    """Prune area of every family candidate, by one-task delta recurrence.

    ``area_0`` is the :func:`left_fold` sum over the first allocation;
    ``area_{i+1} = area_i + (s_new * t(s_new) - s_old * t(s_old))`` via
    ``np.add.accumulate`` — the same IEEE additions whether the recurrence
    runs here or one step at a time, so both evaluators see identical
    values.  O(n + family) total instead of O(n) per candidate.
    """
    area0 = left_fold(0, (s * t.times[s] for t, s in zip(tasks, first)))
    if not deltas:
        return np.array([area0])
    alloc = list(first)
    terms = np.empty(len(deltas))
    for k, (j, s_new) in enumerate(deltas):
        s_old = alloc[j]
        t = tasks[j]
        terms[k] = s_new * t.times[s_new] - s_old * t.times[s_old]
        alloc[j] = s_new
    return np.add.accumulate(np.concatenate(([area0], terms)))


def _winner_scan(
    score: Callable[[int], tuple[float, object]],
    areas: np.ndarray | None,
    eps: float,
    n_slices: int,
    family_size: int,
) -> tuple[tuple[float, int, object], int]:
    """The phase-2 selection loop, shared by every evaluator.

    ``score(i)`` is called for consecutive ``i`` starting at 0 and returns
    ``(makespan, payload)``.  Candidate ``i`` is pruned-past (loop break)
    when an incumbent exists and ``areas[i] / n_slices`` already reaches
    it; the incumbent is replaced only on a strict EPS improvement, so
    ties keep the earliest family index.  Returns the winning
    ``(makespan, index, payload)`` and the number of scored candidates.
    """
    best: tuple[float, int, object] | None = None
    evaluated = 0
    i = 0
    while True:
        if areas is not None and best is not None:
            if areas[i] / n_slices >= best[0] - eps:
                break  # all later allocations have >= area -> dominated
        makespan, payload = score(i)
        evaluated += 1
        if best is None or makespan < best[0] - eps:
            best = (makespan, i, payload)
        if i == family_size - 1:
            break
        i += 1
    assert best is not None
    return best, evaluated


class FamilyEvaluator:
    """Protocol: ``evaluate(tasks, spec, first, deltas, config)``."""

    name = "?"

    def evaluate(
        self,
        tasks: Sequence[Task],
        spec: DeviceSpec,
        first: Allocation,
        deltas: list[tuple[int, int]],
        config,
    ) -> FamilyWinner:
        raise NotImplementedError


# -- sequential reference ---------------------------------------------------


@register_evaluator("sequential")
class SequentialEvaluator(FamilyEvaluator):
    """One warm-started Algorithm-1 simulation per candidate (paper §3.2).

    ``config.use_engine`` selects the warm ``LPTGroups`` + lean
    ``chains_makespan`` pipeline (default) or the cold
    replay-per-candidate reference path; both produce identical winners.
    """

    def evaluate(self, tasks, spec, first, deltas, config):
        groups = LPTGroups(tasks, first, spec) if config.use_engine else None
        alloc = list(first)
        state = {"idx": 0}

        def score(i):
            assert i == state["idx"]
            if groups is not None:
                assignment, node_durs = groups.schedule_with_durs()
                makespan = chains_makespan(
                    spec, assignment.node_tasks, node_durs
                )
            else:
                assignment = list_schedule_allocation(tasks, tuple(alloc), spec)
                makespan = replay(assignment).makespan
            if i < len(deltas):
                j, s_new = deltas[i]
                if groups is not None:
                    groups.move(tasks[j], alloc[j], s_new)
                alloc[j] = s_new
                state["idx"] = i + 1
            return makespan, assignment

        areas = family_areas(tasks, first, deltas) if config.prune else None
        best, evaluated = _winner_scan(
            score, areas, config.eps, spec.n_slices, len(deltas) + 1
        )
        makespan, win, assignment = best
        winner_alloc = list(first)
        for j, s_new in deltas[:win]:
            winner_alloc[j] = s_new
        return FamilyWinner(
            makespan, win, assignment, tuple(winner_alloc), evaluated
        )


# -- incremental delta-replay evaluator -------------------------------------

_SIM_CACHE = IdentityCache(16)  # spec -> _SimContext


@dataclasses.dataclass
class _SimContext:
    """Flat per-spec arrays of Algorithm 1's heap phase (C + Python)."""

    spec: DeviceSpec
    n_nodes: int
    n_sizes: int
    sizeidx: dict              # instance size -> size-axis index
    node_keys: list            # node index -> NodeKey
    ns_list: list              # node index -> size-axis index
    tc_list: list              # size-axis index -> creation charge
    td_list: list
    children: list             # node index -> [child node indices]
    roots: list                # root node indices, spec order
    ns: np.ndarray             # the same, as C-ready arrays
    tc: np.ndarray
    td: np.ndarray
    ch_off: np.ndarray
    ch_idx: np.ndarray
    tree: np.ndarray           # node index -> forest tree index
    n_trees: int


def _sim_context(spec: DeviceSpec) -> _SimContext:
    cached = _SIM_CACHE.get(spec)
    if cached is not None:
        return cached
    nodes = spec.nodes
    sizeidx = {s: k for k, s in enumerate(spec.sizes)}
    index = {node.key: i for i, node in enumerate(nodes)}
    ns_list = [sizeidx[node.size] for node in nodes]
    tc_list = [spec.t_create[s] for s in spec.sizes]
    td_list = [spec.t_destroy[s] for s in spec.sizes]
    children = [[index[c.key] for c in node.children] for node in nodes]
    ch_off = np.zeros(len(nodes) + 1, dtype=np.int32)
    for i, ch in enumerate(children):
        ch_off[i + 1] = ch_off[i] + len(ch)
    flat = [c for ch in children for c in ch]
    tree_list = [node.tree for node in nodes]
    ctx = _SimContext(
        spec, len(nodes), len(spec.sizes), sizeidx,
        [node.key for node in nodes], ns_list, tc_list, td_list, children,
        [index[r.key] for r in spec.roots],
        np.array(ns_list, dtype=np.int32),
        np.array(tc_list), np.array(td_list),
        ch_off, np.array(flat or [0], dtype=np.int32),
        np.array(tree_list, dtype=np.int32),
        max(tree_list) + 1 if tree_list else 1,
    )
    _SIM_CACHE.put(spec, ctx)
    return ctx


def _py_sim(ctx: _SimContext, durs_rows: list, n_tasks: int) -> list:
    """Pure-Python cold run of the C loop: Algorithm 1's heap phase over
    size-indexed duration rows, returning the placement visit trace
    ``[(node index, slice start, slice end), ...]``.  Same pops, same
    IEEE additions, same early stop as ``_fastsim.c`` — the incremental
    evaluator's fallback when no C compiler is available."""
    ns_list = ctx.ns_list
    tc_list = ctx.tc_list
    td_list = ctx.td_list
    children = ctx.children
    INF = float("inf")
    cursor = [0] * ctx.n_sizes
    created = bytearray(ctx.n_nodes)
    lens = [len(r) for r in durs_rows]
    reconfig_end = 0.0
    heap = [(0.0, k, r) for k, r in enumerate(ctx.roots)]
    seq = len(heap)
    remaining = n_tasks
    visits: list[tuple[int, int, int]] = []
    heapreplace = heapq.heapreplace
    heappush = heapq.heappush
    heappop = heapq.heappop
    while heap:
        end, _, nidx = heap[0]
        si = ns_list[nidx]
        cur = cursor[si]
        n_grp = lens[si]
        if cur < n_grp:
            if not created[nidx]:
                if end > reconfig_end:
                    reconfig_end = end
                reconfig_end += tc_list[si]
                end = reconfig_end
                created[nidx] = 1
            L = len(heap)
            if L > 2:
                t1 = heap[1][0]
                t2 = heap[2][0]
                nxt = t2 if t2 < t1 else t1
            elif L == 2:
                nxt = heap[1][0]
            else:
                nxt = INF
            row = durs_rows[si]
            start = cur
            while True:
                end += row[cur]
                cur += 1
                if cur >= n_grp or end >= nxt:
                    break
            cursor[si] = cur
            visits.append((nidx, start, cur))
            remaining -= cur - start
            if not remaining:
                break  # drain pops place nothing: early stop
            heapreplace(heap, (end, seq, nidx))
            seq += 1
        elif remaining:
            if created[nidx]:
                if end > reconfig_end:
                    reconfig_end = end
                reconfig_end += td_list[si]
            ch = children[nidx]
            if ch:
                heapreplace(heap, (end, seq, ch[0]))
                seq += 1
                for c in ch[1:]:
                    heappush(heap, (end, seq, c))
                    seq += 1
            else:
                heappop(heap)
        else:
            break  # every task placed: remaining pops only retire
    return visits


@register_evaluator("incremental")
class IncrementalEvaluator(FamilyEvaluator):
    """Delta-replay family scoring: patch the previous trajectory.

    Consecutive family candidates differ by one task's allocation
    (``allocation_family_deltas``), so their Algorithm-1 trajectories
    share a prefix up to the first heap pop whose outcome the delta
    changes.  While simulating candidate ``i``, the compiled backend
    (:mod:`repro.core.fastsim`) snapshots the live state right before
    that divergence point — derived exactly from the LPT ranks the moved
    task leaves and enters, not from fixed checkpoint strides — and
    candidate ``i+1`` restores the snapshot and replays only the
    suffix.  The per-node duration chains come straight from the visit
    trace and are scored by the same :func:`chains_makespan` left folds
    as the sequential path; the winner's assignment is materialised
    lazily, only when an incumbent improves, with the same strict-EPS
    comparison :func:`_winner_scan` applies.  Bit-identical winners by
    construction: same pops, same IEEE additions, same selection scan.

    Without a C compiler the evaluator degrades to a full pure-Python
    resimulation per candidate (:func:`_py_sim`) — still bit-identical,
    only the speedup is gone.  ``use_engine=False`` delegates to
    sequential like the vectorized path does.
    """

    def evaluate(self, tasks, spec, first, deltas, config):
        if not config.use_engine:
            return EVALUATORS["sequential"].evaluate(
                tasks, spec, first, deltas, config
            )
        from repro.core import fastsim

        lib = fastsim.load()
        n = len(tasks)
        F = len(deltas) + 1
        ctx = _sim_context(spec)
        S, N = ctx.n_sizes, ctx.n_nodes
        sizes = spec.sizes
        sizeidx = ctx.sizeidx
        node_keys = ctx.node_keys
        ns_list = ctx.ns_list
        groups = LPTGroups(tasks, first, spec)
        alloc = list(first)
        eps = config.eps
        # live per-size rows, ordered by size index: LPTGroups mutates
        # these list objects in place, so the references stay current
        durs_rows = [groups._durs[s] for s in sizes]
        ids_rows = [groups._ids[s] for s in sizes]

        if lib is not None:
            lmax = max(1, n)
            gdurs = np.zeros((S, lmax))
            glens = np.zeros(S, dtype=np.int32)
            for k in range(S):
                row = durs_rows[k]
                glens[k] = len(row)
                if row:
                    gdurs[k, : len(row)] = row
            hdt = fastsim.heap_dtype()
            cursor = np.zeros(S, dtype=np.int32)
            created = np.zeros(N, dtype=np.int8)
            exh = np.zeros(S, dtype=np.int8)
            heap = np.zeros(N, dtype=hdt)
            heap_len = np.zeros(1, dtype=np.int32)
            scalars = np.zeros(1)
            counters = np.zeros(3, dtype=np.int64)
            s_cursor = np.zeros_like(cursor)
            s_created = np.zeros_like(created)
            s_exh = np.zeros_like(exh)
            s_heap = np.zeros_like(heap)
            s_heap_len = np.zeros(1, dtype=np.int32)
            s_scalars = np.zeros(1)
            s_counters = np.zeros(3, dtype=np.int64)
            snap_flags = np.zeros(2, dtype=np.int32)
            v_node = np.zeros(max(1, n), dtype=np.int32)
            v_start = np.zeros_like(v_node)
            v_end = np.zeros_like(v_node)
            roots = np.array(ctx.roots, dtype=np.int32)
            # chains_makespan scorer scratch (see fastsim_score)
            sc_act = np.zeros(N, dtype=np.int8)
            sc_sub = np.zeros(N, dtype=np.int8)
            sc_head = np.zeros(N, dtype=np.int32)
            sc_tail = np.zeros(N, dtype=np.int32)
            sc_nxt = np.zeros(max(1, n), dtype=np.int32)
            sc_heap = np.zeros(N, dtype=fastsim.evt_dtype())
            sc_rc = np.zeros(max(1, ctx.n_trees))
            per_tree = 1 if spec.reconfig_scope != "global" else 0

            def _cold():
                R = len(roots)
                cursor[:] = 0
                created[:] = 0
                exh[:] = 0
                heap["end"][:R] = 0.0
                heap["seq"][:R] = np.arange(R)
                heap["nidx"][:R] = roots
                heap_len[0] = R
                scalars[0] = 0.0
                counters[0] = R
                counters[1] = n
                counters[2] = 0

            def _run_c(trig):
                a_si, a_rk, b_si, b_rk, b_visit = trig
                rc = lib.run(
                    cursor.ctypes.data, created.ctypes.data,
                    exh.ctypes.data,
                    heap.ctypes.data, heap_len.ctypes.data,
                    scalars.ctypes.data, counters.ctypes.data,
                    N, S,
                    ctx.ns.ctypes.data, ctx.tc.ctypes.data,
                    ctx.td.ctypes.data, ctx.ch_off.ctypes.data,
                    ctx.ch_idx.ctypes.data,
                    gdurs.ctypes.data, glens.ctypes.data, lmax,
                    a_si, a_rk, b_si, b_rk, b_visit,
                    s_cursor.ctypes.data, s_created.ctypes.data,
                    s_exh.ctypes.data,
                    s_heap.ctypes.data, s_heap_len.ctypes.data,
                    s_scalars.ctypes.data, s_counters.ctypes.data,
                    snap_flags.ctypes.data,
                    v_node.ctypes.data, v_start.ctypes.data,
                    v_end.ctypes.data, len(v_node),
                )
                assert rc == 0, "fastsim visit buffer overflow"

            def _score_c(nv):
                return lib.score(
                    N, S,
                    ctx.ns.ctypes.data, ctx.tree.ctypes.data,
                    per_tree, ctx.n_trees,
                    ctx.tc.ctypes.data, ctx.td.ctypes.data,
                    ctx.ch_off.ctypes.data, ctx.ch_idx.ctypes.data,
                    roots.ctypes.data, len(roots),
                    gdurs.ctypes.data, lmax,
                    v_node.ctypes.data, v_start.ctypes.data,
                    v_end.ctypes.data, nv,
                    sc_act.ctypes.data, sc_sub.ctypes.data,
                    sc_head.ctypes.data, sc_tail.ctypes.data,
                    sc_nxt.ctypes.data, sc_heap.ctypes.data,
                    sc_rc.ctypes.data,
                )

        tasks_by_id = groups.tasks_by_id
        best_state = {"mk": None, "assignment": None, "snap": False}

        def _score_visits(visits):
            node_durs: dict = {}
            for nidx, sv, ev in visits:
                key = node_keys[nidx]
                lst = node_durs.get(key)
                if lst is None:
                    node_durs[key] = durs_rows[ns_list[nidx]][sv:ev]
                else:
                    lst.extend(durs_rows[ns_list[nidx]][sv:ev])
            return chains_makespan(spec, node_durs, node_durs)

        def _materialize(visits):
            node_tasks: dict = {}
            for nidx, sv, ev in visits:
                key = node_keys[nidx]
                lst = node_tasks.get(key)
                if lst is None:
                    node_tasks[key] = ids_rows[ns_list[nidx]][sv:ev]
                else:
                    lst.extend(ids_rows[ns_list[nidx]][sv:ev])
            return Assignment(spec, tasks_by_id, node_tasks)

        state = {"idx": 0}

        def score(i):
            assert i == state["idx"]
            # the *next* delta's divergence trigger, in candidate i's rows
            if i < len(deltas):
                j, s_new = deltas[i]
                s_old = alloc[j]
                task = tasks[j]
                keys_old = groups._keys[s_old]
                r_old = bisect.bisect_left(
                    keys_old, (-task.times[s_old], task.id)
                )
                keys_new = groups._keys[s_new]
                r_new = bisect.bisect_left(
                    keys_new, (-task.times[s_new], task.id)
                )
                trig = (
                    sizeidx[s_old], r_old, sizeidx[s_new], r_new,
                    1 if r_new == len(keys_new) else 0,
                )
            else:
                task = r_old = r_new = None
                trig = (-1, -1, -1, -1, 0)
            if lib is not None:
                if i == 0 or not best_state["snap"]:
                    _cold()
                else:
                    # restore the snapshot taken during candidate i-1
                    L = int(s_heap_len[0])
                    cursor[:] = s_cursor
                    created[:] = s_created
                    exh[:] = s_exh
                    heap[:L] = s_heap[:L]
                    heap_len[0] = L
                    scalars[0] = s_scalars[0]
                    counters[:] = s_counters
                # a snapshot produced by this run is only trustworthy
                # when the run *starts* at a shared-prefix point of the
                # next delta — a resume point past the delta's ranks (or
                # past an exhausted-row pop, for tail appends) would hide
                # an earlier divergence, so disarm and resimulate the
                # next candidate cold instead
                trusted = True
                if trig[0] >= 0:
                    a_si, a_rk, b_si, b_rk, b_visit = trig
                    if (
                        cursor[a_si] > a_rk
                        or cursor[b_si] > b_rk
                        or (b_visit and exh[b_si])
                    ):
                        trig = (-1, -1, -1, -1, 0)
                        trusted = False
                snap_flags[:] = 0
                _run_c(trig)
                nv = int(counters[2])
                best_state["snap"] = trusted and bool(snap_flags[0])
                makespan = _score_c(nv)
                visits = None  # materialised only for improving incumbents
            else:
                visits = _py_sim(ctx, durs_rows, n)
                makespan = _score_visits(visits)
            # mirror _winner_scan's replacement comparison exactly, so
            # the assignment is built only for improving incumbents
            if best_state["mk"] is None or makespan < best_state["mk"] - eps:
                best_state["mk"] = makespan
                if visits is None:
                    visits = list(zip(
                        v_node[:nv].tolist(), v_start[:nv].tolist(),
                        v_end[:nv].tolist(),
                    ))
                best_state["assignment"] = _materialize(visits)
            if i < len(deltas):
                groups.move(task, s_old, s_new)
                alloc[j] = s_new
                if lib is not None:
                    a, b = sizeidx[s_old], sizeidx[s_new]
                    la = int(glens[a])
                    row = gdurs[a]
                    row[r_old:la - 1] = row[r_old + 1:la]
                    row[la - 1] = 0.0
                    glens[a] = la - 1
                    lb = int(glens[b])
                    row = gdurs[b]
                    row[r_new + 1:lb + 1] = row[r_new:lb]
                    row[r_new] = task.times[s_new]
                    glens[b] = lb + 1
                state["idx"] = i + 1
            return makespan, None

        areas = family_areas(tasks, first, deltas) if config.prune else None
        best, evaluated = _winner_scan(
            score, areas, config.eps, spec.n_slices, F
        )
        makespan, win, _ = best
        winner_alloc = list(first)
        for j, s_new in deltas[:win]:
            winner_alloc[j] = s_new
        return FamilyWinner(
            makespan, win, best_state["assignment"], tuple(winner_alloc),
            evaluated,
        )


# -- parallel family sharding -----------------------------------------------

#: candidates per worker chunk on pruned runs (the prune break usually
#: lands inside the first chunk, so small chunks bound wasted scoring)
PARALLEL_PRUNED_CHUNK = 32


def _chunk_scores(payload):
    """Full Algorithm-1 scores of family chunk ``[lo, hi)`` (the parallel
    evaluator's pool worker, and the vectorized evaluator's host check).

    Warm-starts :class:`LPTGroups` at candidate ``lo`` (the maintained
    order is bit-identical to a cold sort) and scores every candidate of
    the chunk with the exact sequential pipeline — no pruning in the
    worker, the parent's reduce owns the selection semantics.
    """
    tasks, spec, first, deltas, lo, hi = payload
    alloc = list(first)
    for j, s_new in deltas[:lo]:
        alloc[j] = s_new
    groups = LPTGroups(tasks, tuple(alloc), spec)
    out = []
    for i in range(lo, hi):
        assignment, node_durs = groups.schedule_with_durs()
        out.append(chains_makespan(spec, assignment.node_tasks, node_durs))
        if i < len(deltas):
            j, s_new = deltas[i]
            groups.move(tasks[j], alloc[j], s_new)
            alloc[j] = s_new
    return out


@register_evaluator("parallel")
class ParallelEvaluator(FamilyEvaluator):
    """Process-pool family sharding with a deterministic ordered reduce.

    The family is cut into contiguous index chunks; pool workers score
    whole chunks with the sequential pipeline (LPT warm-start inside the
    chunk, no pruning) and return plain makespan lists.  The parent
    walks those scores through the shared :func:`_winner_scan` in family
    order, so the prune break, the strict-EPS incumbent rule, the
    family-index tie-break and the ``evaluated`` count are reproduced
    bit-identically no matter how many workers run or in which order
    chunks complete — results are keyed by chunk index, never by
    arrival.  Only the winner is resimulated (once, in-process) to
    materialise its assignment.

    ``SchedulerConfig(parallel_workers=)`` sizes the pool (0 = all
    cores); one worker or a one-candidate family short-circuits to the
    sequential evaluator.  Chunks are dispatched lazily a pool-width
    ahead of the scan so pruned runs do not score the whole family.

    Like any forkserver/spawn ``multiprocessing`` use, calling this
    evaluator from a script requires the usual
    ``if __name__ == "__main__":`` entry guard — the workers re-import
    ``__main__``.
    """

    def evaluate(self, tasks, spec, first, deltas, config):
        workers = getattr(config, "parallel_workers", 0) or (
            os.cpu_count() or 1
        )
        F = len(deltas) + 1
        if not config.use_engine or workers <= 1 or F <= 1:
            return EVALUATORS["sequential"].evaluate(
                tasks, spec, first, deltas, config
            )
        import concurrent.futures as cf
        import multiprocessing as mp

        # fork would clone whatever thread pools the parent has running
        # (jax's in particular — a known deadlock); the forkserver is a
        # clean process forked before any of that, with spawn as the
        # portable fallback
        try:
            mp_ctx = mp.get_context("forkserver")
        except ValueError:  # pragma: no cover - platform without it
            mp_ctx = mp.get_context("spawn")

        chunk = (
            PARALLEL_PRUNED_CHUNK if config.prune
            else max(1, -(-F // workers))
        )
        bounds = [
            (lo, min(lo + chunk, F)) for lo in range(0, F, chunk)
        ]
        scores: dict[int, float] = {}
        futures: dict[int, object] = {}
        submitted = {"next": 0}

        with cf.ProcessPoolExecutor(
            max_workers=workers, mp_context=mp_ctx
        ) as pool:

            def _submit_ahead(upto_chunk: int) -> None:
                # keep a pool-width of chunks in flight past the scan
                while (
                    submitted["next"] < len(bounds)
                    and submitted["next"] <= upto_chunk + workers
                ):
                    lo, hi = bounds[submitted["next"]]
                    futures[submitted["next"]] = pool.submit(
                        _chunk_scores,
                        (tasks, spec, first, deltas, lo, hi),
                    )
                    submitted["next"] += 1

            def score(i):
                k = i // chunk
                _submit_ahead(k)
                if i not in scores:
                    lo = bounds[k][0]
                    for off, mk in enumerate(futures[k].result()):
                        scores[lo + off] = mk
                return scores[i], None

            areas = (
                family_areas(tasks, first, deltas) if config.prune else None
            )
            best, evaluated = _winner_scan(
                score, areas, config.eps, spec.n_slices, F
            )
        makespan, win, _ = best
        winner_alloc = list(first)
        for j, s_new in deltas[:win]:
            winner_alloc[j] = s_new
        # one in-process resimulation materialises the winner (the
        # maintained LPT order is bit-identical to this cold build)
        assignment = LPTGroups(
            tasks, tuple(winner_alloc), spec
        ).schedule()
        return FamilyWinner(
            makespan, win, assignment, tuple(winner_alloc), evaluated
        )


# -- vectorized array program -----------------------------------------------

_SPEC_CACHE = IdentityCache(16)       # spec -> _SpecArrays
_PROGRAM_CACHE = IdentityCache(64)    # (spec, kind, C, L) -> jitted program

_BIG_SEQ = np.int32(2**30)


class DeviceMismatchError(RuntimeError):
    """The device's float64 results differ from the host's IEEE ones.

    Raised by ``evaluator="vectorized"`` off the CPU backend, where float64
    may be emulated (the TPU computes it with pairs of float32), instead of
    returning a winner that could differ from ``"sequential"``."""


@dataclasses.dataclass
class _SpecArrays:
    """Per-spec constants of the lockstep program (spec.nodes BFS order)."""

    spec: DeviceSpec
    n_nodes: int
    n_sizes: int
    node_sizeidx: np.ndarray   # (N,) size-axis index per node
    node_keys: list            # (N,) NodeKey per node
    size_onehot: np.ndarray    # (N, S) bool: node n has size s
    tc: np.ndarray             # (N,) creation charge per node
    td: np.ndarray             # (N,) destruction charge per node
    nch: np.ndarray            # (N,) child count per node
    childmask: np.ndarray      # (N, N) bool: [p, c] c is a child of p
    childrank: np.ndarray      # (N, N) int32: push rank of child c of p
    theap0: np.ndarray         # (N,) initial heap times (roots 0, else inf)
    tseq0: np.ndarray          # (N,) initial heap seqs (roots 0..R-1)
    seq0: int                  # first free seq (= number of roots)


def _spec_eval_arrays(spec: DeviceSpec) -> _SpecArrays:
    cached = _SPEC_CACHE.get(spec)
    if cached is not None:
        return cached
    nodes = spec.nodes
    N = len(nodes)
    S = len(spec.sizes)
    sizeidx = {s: k for k, s in enumerate(spec.sizes)}
    index = {node.key: i for i, node in enumerate(nodes)}
    node_sizeidx = np.array([sizeidx[node.size] for node in nodes])
    size_onehot = np.zeros((N, S), dtype=bool)
    size_onehot[np.arange(N), node_sizeidx] = True
    childmask = np.zeros((N, N), dtype=bool)
    childrank = np.zeros((N, N), dtype=np.int32)
    for i, node in enumerate(nodes):
        for rank, child in enumerate(node.children):
            childmask[i, index[child.key]] = True
            childrank[i, index[child.key]] = rank
    theap0 = np.full(N, np.inf)
    tseq0 = np.full(N, _BIG_SEQ, dtype=np.int32)
    roots = [index[r.key] for r in spec.roots]
    for rank, i in enumerate(roots):
        theap0[i] = 0.0
        tseq0[i] = rank
    out = _SpecArrays(
        spec, N, S, node_sizeidx, [node.key for node in nodes], size_onehot,
        np.array([spec.t_create[node.size] for node in nodes]),
        np.array([spec.t_destroy[node.size] for node in nodes]),
        np.array([len(node.children) for node in nodes], dtype=np.int32),
        childmask, childrank, theap0, tseq0, len(roots),
    )
    _SPEC_CACHE.put(spec, out)
    return out


def _phase_a_program(sa: _SpecArrays, C: int, L: int) -> Callable:
    """Jitted lockstep Algorithm 1 over a ``(C, S, L)`` duration tensor.

    One step = one heap pop per candidate, in lockstep: a masked
    ``(time, seq)`` argmin over the node axis replaces the heap (the tree
    is tiny, so every node holds at most one pending entry), placement
    advances the popped size's cursor by one task, exhausted nodes
    repartition into their children or retire.  One-at-a-time placement
    pops in exactly the same order as the sequential runs-with-shortcut
    code (see ``_list_schedule_arrays``), and every reconfiguration /
    chain addition is a single f64 op in the same order, so the recorded
    pops are bit-identical to the sequential simulation.  The popped
    node's constants are read with masked selects over the one-hot pop
    mask — selections, never products — so no rounding enters there.
    Total steps are bounded by ``n + N``: every task is placed exactly
    once and each node leaves the heap at most once.

    Returns ``run(gdurs, glen) -> (nid, chain_durs, chain_len)``: the
    ``(T, C)`` record of the node each step placed a task on (-1 when it
    placed none), and every candidate's per-node duration chains as a
    zero-padded ``(C, N, L)`` tensor with its ``(C, N)`` lengths — the
    input of :func:`_chains_program`.  The program is a ``lax.scan``
    (stacked step outputs write into a preallocated buffer; a recording
    while_loop carry would copy the whole record every iteration).
    Call, trace and run it inside ``jax.enable_x64(True)``.
    """
    cached = _PROGRAM_CACHE.get(sa.spec, ("phase_a", C, L))
    if cached is not None:
        return cached
    jax, jnp = _jax_modules()
    N = sa.n_nodes
    S = sa.n_sizes
    T = L + N
    INF = np.inf

    @jax.jit
    def run(gdurs, glen):
        size_onehot = jnp.asarray(sa.size_onehot)[None]     # (1, N, S)
        childmask = jnp.asarray(sa.childmask)[None]         # (1, N, N)
        childrank = jnp.asarray(sa.childrank)[None]         # (1, N, N)
        tc_n = jnp.asarray(sa.tc)
        td_n = jnp.asarray(sa.td)
        nch_n = jnp.asarray(sa.nch)
        sizebase = jnp.asarray(np.arange(S, dtype=np.int32) * L)[None, :]
        gflat = gdurs.reshape(C, S * L)

        def body(st, _):
            (theap, tseq, seqctr, cursor, dnext, re, has, rem, ccnt) = st
            # pop: lexicographic (time, seq) min per candidate
            tmin = theap.min(1, keepdims=True)
            candm = theap == tmin
            seqm = jnp.where(candm, tseq, _BIG_SEQ)
            sel = candm & (seqm == seqm.min(1, keepdims=True))
            # the popped node's constants: sel is one-hot on live rows
            sel3 = sel[:, :, None]
            sel_s = (sel3 & size_onehot).any(1)
            tc = jnp.where(sel, tc_n, 0.0).sum(1, keepdims=True)
            td = jnp.where(sel, td_n, 0.0).sum(1, keepdims=True)
            nch = jnp.where(sel, nch_n, 0).sum(1, keepdims=True,
                                                dtype=jnp.int32)
            chmask = (sel3 & childmask).any(1)
            chrank = jnp.where(sel3, childrank, 0).sum(1, dtype=jnp.int32)
            nid = jnp.argmax(sel, 1).astype(jnp.int32)

            alive = jnp.isfinite(tmin)
            place = (sel_s & (cursor < glen)).any(1, keepdims=True) & alive
            d = jnp.where(sel_s, dnext, 0.0).sum(1, keepdims=True)
            hasn = (sel & has).any(1, keepdims=True)
            create = place & ~hasn
            # the serialized reconfiguration tail (creation on first task,
            # destruction on repartitioning a used node)
            re_c = jnp.maximum(re, tmin) + tc
            start = jnp.where(create, re_c, tmin)
            end = start + d
            repart = alive & ~place & (rem > 0)
            destroy = repart & hasn
            re_d = jnp.maximum(re, tmin) + td
            re = jnp.where(create, re_c, jnp.where(destroy, re_d, re))
            # heap: placement re-pushes the node at its chain end; a
            # repartition replaces it by its children; a retire drops it
            theap = jnp.where(sel, jnp.where(place, end, INF), theap)
            theap = jnp.where(repart & chmask, tmin, theap)
            tseq = jnp.where(sel & place, seqctr, tseq)
            tseq = jnp.where(repart & chmask, seqctr + chrank, tseq)
            seqctr = seqctr + jnp.where(place, 1, jnp.where(repart, nch, 0))
            has = has | (sel & create)
            pos = jnp.where(sel, ccnt, 0).sum(1, dtype=jnp.int32)
            ccnt = ccnt + (sel & place).astype(jnp.int32)
            adv = sel_s & place
            cursor = cursor + adv.astype(jnp.int32)
            # one scalar lookup per candidate (vmapped dynamic_slice beats
            # a (C, S) take_along_axis on the CPU backend)
            flatidx = jnp.where(
                sel_s, sizebase + jnp.minimum(cursor, L - 1), 0
            ).sum(1, dtype=jnp.int32)
            gd = jax.vmap(
                lambda row, i: jax.lax.dynamic_slice(row, (i,), (1,))[0]
            )(gflat, flatidx)
            dnext = jnp.where(adv, gd[:, None], dnext)
            rem = rem - place.astype(jnp.int32)
            pl = place[:, 0]
            rec = (
                jnp.where(pl, nid, -1),
                jnp.where(pl, d[:, 0], 0.0),
                jnp.where(pl, pos, 0),
            )
            return (theap, tseq, seqctr, cursor, dnext, re, has, rem,
                    ccnt), rec

        st = (
            jnp.broadcast_to(sa.theap0, (C, N)),
            jnp.broadcast_to(sa.tseq0, (C, N)),
            jnp.full((C, 1), sa.seq0, jnp.int32),
            jnp.zeros((C, S), jnp.int32),
            gdurs[:, :, 0],
            jnp.zeros((C, 1)),
            jnp.zeros((C, N), bool),
            glen.sum(1, keepdims=True),
            jnp.zeros((C, N), jnp.int32),
        )
        st, (nid, dur, pos) = jax.lax.scan(body, st, None, length=T)
        # scatter the placements into per-node chains (node index N, for
        # steps that placed nothing, falls outside and is dropped)
        cols = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (T, C))
        node = jnp.where(nid >= 0, nid, N)
        chain_durs = jnp.zeros((C, N, L)).at[cols, node, pos].set(
            dur, mode="drop"
        )
        return nid, chain_durs, st[-1]

    _PROGRAM_CACHE.put(sa.spec, run, ("phase_a", C, L))
    return run


def _chains_program(spec: DeviceSpec, C: int, L: int) -> Callable:
    """Jitted :func:`~repro.core.timing.chains_makespan_batch`: the
    replay-semantics event walk over ``C`` candidates' ``(C, N, L)``
    zero-padded duration chains and ``(C, N)`` lengths, returning the
    ``(C,)`` makespans bit-identical to ``chains_makespan`` per candidate.

    The tree is tiny, so the event queue holds at most one pending event
    per node and a pop is a masked ``(when, seq)`` argmin over the node
    axis; every node contributes at most one visit and one done pop, so
    ``2 * N`` steps drain every walk (trailing steps are masked no-ops).
    Bit-exactness is by construction: the chain fold is a sequential loop
    of float64 additions (a left fold, never an associative scan, whose
    re-association would change roundings; the zero padding adds exact
    ``+0.0``), and the popped node's values are read by gathers and
    masked selects, which do no arithmetic.  Call, trace and run it
    inside ``jax.enable_x64(True)``.
    """
    cached = _PROGRAM_CACHE.get(spec, ("chains", C, L))
    if cached is not None:
        return cached
    jax, jnp = _jax_modules()
    (tc, td, childmask, descmask, root_idx, grp_idx,
     n_groups) = _batch_spec_arrays(spec)
    N = len(spec.nodes)
    BIG = _BIG_SEQ
    INF = np.inf

    @jax.jit
    def walk(durs, lens):
        tc_n, td_n = jnp.asarray(tc), jnp.asarray(td)
        child = jnp.asarray(childmask)
        grp = jnp.asarray(grp_idx)
        active = lens > 0                                       # (C, N)
        # sub_act[c, a]: an active node in subtree(a); goflag[c, p]: a
        # child of p has an active subtree
        sub_act = (active[:, None, :] & jnp.asarray(descmask)[None]).any(2)
        goflag = (sub_act[:, None, :] & child[None]).any(2)
        tevt = jnp.full((C, N), INF)
        sevt = jnp.full((C, N), BIG, jnp.int32)
        wevt = jnp.zeros((C, N), jnp.int32)                     # 0 visit, 1 done
        seqctr = jnp.zeros((C,), jnp.int32)
        for i in root_idx:  # roots pushed in spec order, seq 0, 1, ...
            pushed = sub_act[:, i]
            tevt = tevt.at[:, i].set(jnp.where(pushed, 0.0, INF))
            sevt = sevt.at[:, i].set(jnp.where(pushed, seqctr, BIG))
            seqctr = seqctr + pushed.astype(jnp.int32)
        iota_n = np.arange(N)[None, :]
        iota_g = np.arange(n_groups)[None, :]

        def pick(a, n_star):
            return jnp.take_along_axis(a, n_star[:, None], 1)[:, 0]

        def step(_, carry):
            tevt, sevt, wevt, seqctr, re, mk = carry
            rows = jnp.isfinite(tevt).any(1)
            when = tevt.min(1)
            cand = tevt == when[:, None]
            seqm = jnp.where(cand, sevt, BIG)
            sel = cand & (seqm == seqm.min(1)[:, None]) & rows[:, None]
            n_star = jnp.argmax(sel, 1)
            onehot = iota_n == n_star[:, None]
            oh_g = iota_g == grp[n_star][:, None]
            re_cur = jnp.where(oh_g, re, 0.0).sum(1)
            what = pick(wevt, n_star)
            act = pick(active, n_star)
            m_visit = rows & (what == 0)
            m_va = m_visit & act
            m_done = rows & (what == 1)

            # visit of an active node: creation charge + exact chain fold
            t0 = jnp.maximum(re_cur, when) + tc_n[n_star]
            chosen = jnp.take_along_axis(
                durs, n_star[:, None, None], 1
            )[:, 0]                                             # (C, L)
            flen = jnp.where(m_va, pick(lens, n_star), 0).max()
            end = jax.lax.fori_loop(
                0, flen, lambda l, t: t + chosen[:, l], t0
            )
            re = jnp.where(oh_g & m_va[:, None], t0[:, None], re)
            mk = jnp.where(m_va & (end > mk), end, mk)
            # visit -> done event in place (chain end if active, else when)
            upd_v = onehot & m_visit[:, None]
            tevt = jnp.where(upd_v, jnp.where(m_va, end, when)[:, None],
                             tevt)
            wevt = jnp.where(upd_v, 1, wevt)
            sevt = jnp.where(upd_v, seqctr[:, None], sevt)
            seqctr = seqctr + m_visit.astype(jnp.int32)

            # done: destroy (active node, active subtree remains) + children
            m_dgo = m_done & pick(goflag, n_star)
            m_destroy = m_dgo & act
            re_d = jnp.maximum(re_cur, when) + td_n[n_star]
            re = jnp.where(oh_g & m_destroy[:, None], re_d[:, None], re)
            tevt = jnp.where(onehot & m_done[:, None], INF, tevt)
            push = child[n_star] & sub_act & m_dgo[:, None]
            rank = jnp.cumsum(push, 1, dtype=jnp.int32) - 1
            tevt = jnp.where(push, when[:, None], tevt)
            wevt = jnp.where(push, 0, wevt)
            sevt = jnp.where(push, seqctr[:, None] + rank, sevt)
            seqctr = seqctr + push.sum(1, dtype=jnp.int32)
            return tevt, sevt, wevt, seqctr, re, mk

        carry = (tevt, sevt, wevt, seqctr, jnp.zeros((C, n_groups)),
                 jnp.zeros((C,)))
        return jax.lax.fori_loop(0, 2 * N, step, carry)[5]

    _PROGRAM_CACHE.put(spec, walk, ("chains", C, L))
    return walk


def _pow2(x: int) -> int:
    return 1 << max(1, (x - 1).bit_length())


@register_evaluator("vectorized")
class VectorizedEvaluator(FamilyEvaluator):
    """Chunked array-program scorer (module docstring has the design).

    Scores candidates in growing chunks through the jitted lockstep and
    the jitted chain walk, both on jax's default device; the shared
    :func:`_winner_scan` then walks the scores with the same
    prune/incumbent comparisons as the sequential path, so extra
    chunk-tail candidates cost time but never change the selection.
    Only the winner's assignment is materialised (task ids resolved from
    the membership row + recorded pop sequence).

    Off the CPU backend every scored chunk and the winner's assignment
    are checked against the sequential pipeline on the host, and the
    first difference raises :class:`DeviceMismatchError`: a device whose
    float64 is not IEEE (the TPU emulates it) must not pick a different
    winner, and the evaluator does not fall back to another path.
    """

    def evaluate(self, tasks, spec, first, deltas, config):
        if not HAVE_JAX:
            raise RuntimeError(
                "evaluator='vectorized' needs jax, which is not installed; "
                "use evaluator='sequential' or 'auto'"
            )
        jax, jnp = _jax_modules()
        platform = _platform()
        n = len(tasks)
        F = len(deltas) + 1
        sa = _spec_eval_arrays(spec)
        S, N = sa.n_sizes, sa.n_nodes
        orders = size_sorted_orders(tasks, spec)
        sizeidx = {s: k for k, s in enumerate(spec.sizes)}
        L = _pow2(n)

        # membership of each batch position in its per-size sorted order,
        # advanced chunk by chunk through the family deltas
        member = np.zeros((S, n), dtype=bool)
        rows = np.array([sizeidx[s] for s in first])
        member[rows, orders.inv[rows, np.arange(n)]] = True
        # delta column flips in sorted-position space: (size row, position)
        alloc = list(first)
        flips = []  # per delta: (row_old, pos_old, row_new, pos_new)
        for j, s_new in deltas:
            s_old = alloc[j]
            flips.append((
                sizeidx[s_old], orders.inv[sizeidx[s_old], j],
                sizeidx[s_new], orders.inv[sizeidx[s_new], j],
            ))
            alloc[j] = s_new

        # every chunk pays a full (n + N)-step scan regardless of its
        # width, so the schedule is: without pruning score the whole
        # family at once; with pruning one prune-window-sized chunk
        # first (the admissible prune usually stops within a few dozen
        # candidates), then geometrically growing remainders.  Only the
        # most recent chunk's pop records are retained — the scan keeps
        # the incumbent winner's single record column as its payload.
        first_chunk = min(F, MAX_CHUNK) if config.prune \
            else min(F, MAX_FAMILY_CHUNK)
        state = {"next": 0, "size": first_chunk, "scores": {},
                 "chunk": None}  # (i0, member at i0, pop node ids (T, C))

        def score_chunk(i0: int, count: int) -> None:
            # pad the candidate axis to a multiple of 32 (few compiled
            # variants, little waste — padded rows have no tasks and
            # retire in a handful of steps)
            Cb = max(8, -(-count // 32) * 32) if count > 8 else 8
            mem0 = member.copy()
            # duration tensor: candidate i0's rows by direct compress of
            # the base membership, then each next candidate as a copy of
            # the previous one with the one-task delta applied as two
            # shifted-row edits (delete at old LPT rank, insert at new)
            gdurs = np.zeros((Cb, S, L))
            glen = np.zeros((Cb, S), dtype=np.int32)
            for si in range(S):
                dsel = orders.durs[si][member[si]]
                gdurs[0, si, : len(dsel)] = dsel
                glen[0, si] = len(dsel)
            for k in range(1, count):
                ro, po, rn, pn = flips[i0 + k - 1]
                gdurs[k] = gdurs[k - 1]
                glen[k] = glen[k - 1]
                r_o = int(member[ro, :po].sum())
                lo = int(glen[k, ro])
                row = gdurs[k, ro]
                row[r_o:lo - 1] = row[r_o + 1:lo]
                row[lo - 1] = 0.0
                glen[k, ro] = lo - 1
                member[ro, po] = False
                r_n = int(member[rn, :pn].sum())
                ln = int(glen[k, rn])
                row = gdurs[k, rn]
                row[r_n + 1:ln + 1] = row[r_n:ln]
                row[r_n] = orders.durs[rn][pn]
                glen[k, rn] = ln + 1
                member[rn, pn] = True
            # advance the base membership past this chunk's last candidate
            if i0 + count - 1 < len(flips):
                ro, po, rn, pn = flips[i0 + count - 1]
                member[ro, po] = False
                member[rn, pn] = True
            # constants, tracing and execution must all sit inside the
            # x64 scope, or the programs silently truncate to float32
            with jax.enable_x64(True):
                run = _phase_a_program(sa, Cb, L)
                walk = _chains_program(spec, Cb, L)
                nid_j, cd_j, cl_j = run(jnp.asarray(gdurs), jnp.asarray(glen))
                scores = np.asarray(walk(cd_j, cl_j))
                nid = np.asarray(nid_j)[: n + N]              # (T, Cb)
            if platform != "cpu":
                ref = _chunk_scores((tasks, spec, first, deltas, i0,
                                     i0 + count))
                for k in range(count):
                    if scores[k] != ref[k]:
                        raise DeviceMismatchError(
                            f"evaluator='vectorized' on {platform}: family "
                            f"candidate {i0 + k} scores {float(scores[k])!r} "
                            f"on the device but {ref[k]!r} on the host"
                        )
            for k in range(count):
                state["scores"][i0 + k] = float(scores[k])
            state["chunk"] = (i0, mem0, nid)

        def score(i):
            while i >= state["next"]:
                count = min(state["size"], F - state["next"])
                score_chunk(state["next"], count)
                state["next"] += count
                # geometric growth bounds over-scoring past the prune
                # break to ~the last chunk's width
                state["size"] = max(
                    1, min(state["size"] * 4, F - state["next"],
                           MAX_FAMILY_CHUNK)
                )
            i0, mem0, nid = state["chunk"]
            return state["scores"][i], (i0, mem0, nid[:, i - i0].copy())

        areas = family_areas(tasks, first, deltas) if config.prune else None
        best, evaluated = _winner_scan(
            score, areas, config.eps, spec.n_slices, F
        )
        makespan, win, payload = best
        assignment = self._winner_assignment(
            tasks, spec, sa, orders, payload, flips, win
        )
        winner_alloc = list(first)
        for j, s_new in deltas[:win]:
            winner_alloc[j] = s_new
        if platform != "cpu":
            host = LPTGroups(tasks, tuple(winner_alloc), spec).schedule()
            if host.node_tasks != assignment.node_tasks:
                raise DeviceMismatchError(
                    f"evaluator='vectorized' on {platform}: the winning "
                    f"family candidate {win} is placed differently on the "
                    f"device than on the host"
                )
        return FamilyWinner(
            makespan, win, assignment, tuple(winner_alloc), evaluated
        )

    @staticmethod
    def _winner_assignment(tasks, spec, sa, orders, payload, flips, win):
        """Task-id chains of the winning candidate, in the exact node
        creation order the sequential simulation produces.  ``payload``
        is the scan-retained ``(chunk start, membership at chunk start,
        winner's pop-record column)``."""
        i0, mem0, pops = payload
        member_w = mem0.copy()
        for k in range(i0, win):
            ro, po, rn, pn = flips[k]
            member_w[ro, po] = False
            member_w[rn, pn] = True
        seqn = pops[pops >= 0]                 # node index per placement
        sidx = sa.node_sizeidx[seqn]
        pos = np.empty(len(seqn), dtype=np.int64)
        ids_w = {}
        for si in range(sa.n_sizes):
            m = sidx == si
            pos[m] = np.arange(m.sum())
            ids_w[si] = orders.ids[si][member_w[si]]
        node_tasks: dict = {}
        first_step = {}
        for nn in np.unique(seqn):
            first_step[nn] = int(np.argmax(seqn == nn))
        for nn in sorted(first_step, key=first_step.get):
            m = seqn == nn
            si = int(sa.node_sizeidx[nn])
            node_tasks[sa.node_keys[nn]] = ids_w[si][pos[m]].tolist()
        tasks_by_id = {t.id: t for t in tasks}
        return Assignment(spec, tasks_by_id, node_tasks)


__all__ = [
    "AUTO_MIN_FAMILY",
    "AUTO_MIN_TASKS",
    "AUTO_MIN_TASKS_INCREMENTAL",
    "AUTO_MIN_TASKS_UNPRUNED",
    "DeviceMismatchError",
    "EVALUATORS",
    "FamilyEvaluator",
    "FamilyWinner",
    "HAVE_JAX",
    "IncrementalEvaluator",
    "ParallelEvaluator",
    "SequentialEvaluator",
    "VectorizedEvaluator",
    "family_areas",
    "get_evaluator",
    "register_evaluator",
    "resolve_evaluator",
]
