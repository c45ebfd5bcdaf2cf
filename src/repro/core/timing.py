"""Incremental timing engine for the FAR hot path.

:func:`~repro.core.repartition.replay` is the repo's single timing
authority, but it rebuilds the full :class:`~repro.core.problem.Schedule`
(one ``ScheduledTask`` object per task, one event per tree node) on every
call.  Phase-3 refinement, the §4.3 seam move/swap engine and the online
scheduler all evaluate *many* small edits of one assignment, so they paid
a full replay per candidate — the dominant scheduler cost in
``benchmarks/t_cost.py``.

:class:`TimingEngine` is a mutable evaluator over the same state replay
consumes (per-node task chains + the device tree + the seam carry-over
``release``/``alive``/``direction`` context).  It supports

* ``apply_move(tid, dst[, src])`` / ``apply_swap(tk, tj)`` /
  ``apply_append(tid, key)`` — the exact chain edits phases 3 and §4.3
  perform (LPT-position inserts identical to theirs);
* ``apply_retract(tid)`` / ``retract_suffix(key, n)`` — the inverse of
  append: pull a not-yet-started suffix back off a chain (serving
  re-planning withdraws queued placements when a flush lands);
* ``apply_stretch(tid, duration)`` — override one task's duration with
  runtime truth (actual completion, straggler projection): the
  closed-loop feedback correction, logged and undo-exact like every
  other edit; ``schedule()`` marks corrected items via
  ``ScheduledTask.end_override``;
* ``apply_cancel(tid, duration)`` / ``apply_credit(tid, credit_s)`` —
  the speculation/checkpoint primitives: truncate a slot into a failed
  occupancy record (the losing attempt of a first-finisher race), or
  shorten a not-yet-started retry by its banked checkpoint credit;
* ``undo()`` — speculative evaluation: apply an edit, read the timing,
  undo, bit-for-bit back to the previous state;
* ``makespan()`` / ``slice_end_times()`` / ``node_end_times()`` /
  ``begin_mass()`` — timings of the *current* chains.

**Replay-equivalence contract:** for any assignment state and any
``(release, alive, direction, include_reconfig)`` context, every accessor
returns exactly what a fresh ``replay()`` of the same assignment would
yield — bit-for-bit, not just within EPS.  The engine achieves this by
running the same event simulation with the same heap tie-breaking and the
same float-addition order, but at *node granularity*: chains contribute a
cached duration list (updated incrementally on each edit) instead of
per-task ``ScheduledTask`` objects, and only the affected nodes' chains
plus the sequential reconfiguration tail are touched per edit.  The
contract is enforced by ``tests/test_timing_engine.py`` against randomized
edit sequences in all four context combinations.

:class:`ReplayEngine` is the reference implementation of the same mutable
API, scoring every query with a full replay — it exists so the consumers
can be flipped between the two (``use_engine=`` flags) and compared.
"""

from __future__ import annotations

import dataclasses
import heapq
import math
from bisect import bisect_left
from typing import Sequence

from repro.core.device_spec import DeviceSpec, InstanceNode
from repro.core.problem import ReconfigEvent, Schedule, ScheduledTask
from repro.core.repartition import (
    Assignment,
    NodeKey,
    reconfig_sequence_starts,
    replay,
)


def left_fold(start: float, values) -> float:
    """``((start + v0) + v1) + ...``, added strictly left to right.

    This is the summation rule of every timing path: replay, the timing
    engine, ``_fastsim.c`` and the vectorized device programs all fold
    chains this way, so their results agree bit for bit.  ``sum()`` is
    not this fold: since Python 3.12 it compensates float rounding
    (Neumaier), which can differ in the last bit.
    """
    for v in values:
        start += v
    return start


def _lpt_insert_pos(lst: list[int], tid: int, tasks, size: int) -> int:
    """Insert position keeping ``lst`` LPT-ordered (desc by duration), the
    invariant phase 3 / §4.3 maintain on every node's task list."""
    times = [-tasks[t].times[size] for t in lst]
    return bisect_left(times, -tasks[tid].times[size])


class ChainState:
    """Mutable per-node task chains with an undo log.

    Owns a working copy of an :class:`Assignment`'s ``node_tasks`` (the
    ``tasks`` dict and spec are shared — tasks are immutable).  All edits go
    through ``apply_*`` so subclasses can invalidate timing caches, and every
    edit records exact list positions so ``undo()`` restores bit-identical
    state (including tie order within equal durations).
    """

    def __init__(self, assignment: Assignment, copy_chains: bool = True):
        self.spec: DeviceSpec = assignment.spec
        self.tasks = assignment.tasks
        if copy_chains:
            self.chains: dict[NodeKey, list[int]] = {
                k: list(v) for k, v in assignment.node_tasks.items()
            }
        else:
            self.chains = assignment.node_tasks
        # cached per-chain duration lists, kept aligned with self.chains
        self.durs: dict[NodeKey, list[float]] = {
            k: [self.tasks[t].times[k[2]] for t in v]
            for k, v in self.chains.items()
        }
        # runtime duration corrections (tid -> actual/projected seconds);
        # consulted whenever a chain slot is (re)built so undo of a
        # retract/extract restores the corrected duration, not the profile
        self.stretched: dict[int, float] = {}
        # tids whose slot is a *cancelled occupancy record* (the losing
        # attempt of a speculation race): the slice stays busy for the
        # truncated span but the task did not complete there, so
        # schedule() materialises the slot with failed=True
        self.cancelled: set[int] = set()
        self._task_node: dict[int, NodeKey] | None = None  # built lazily
        self._chain_ver: dict[NodeKey, int] = {}  # bumped per chain edit
        self._log: list[tuple] = []

    @property
    def task_node(self) -> dict[int, NodeKey]:
        """tid -> hosting node key (lazy: query-only engines skip it)."""
        if self._task_node is None:
            self._task_node = {
                tid: k for k, lst in self.chains.items() for tid in lst
            }
        return self._task_node

    def _bump(self, key: NodeKey) -> None:
        self._chain_ver[key] = self._chain_ver.get(key, 0) + 1

    # -- views --------------------------------------------------------------
    @property
    def assignment(self) -> Assignment:
        """Live (zero-copy) Assignment view of the current chains."""
        return Assignment(self.spec, self.tasks, self.chains)

    def export_assignment(self) -> Assignment:
        return Assignment(
            self.spec, dict(self.tasks), {k: list(v) for k, v in self.chains.items()}
        )

    # -- primitive list surgery --------------------------------------------
    def _remove(self, key: NodeKey, tid: int) -> int:
        lst = self.chains[key]
        idx = lst.index(tid)
        lst.pop(idx)
        self.durs[key].pop(idx)
        self._bump(key)
        return idx

    def _insert(self, key: NodeKey, idx: int, tid: int) -> None:
        self.chains.setdefault(key, [])
        self.durs.setdefault(key, [])
        self.chains[key].insert(idx, tid)
        dur = self.stretched.get(tid)
        if dur is None:
            dur = self.tasks[tid].times[key[2]]
        self.durs[key].insert(idx, dur)
        self._bump(key)
        if self._task_node is not None:
            self._task_node[tid] = key

    # -- edits --------------------------------------------------------------
    def apply_move(self, tid: int, dst: NodeKey, src: NodeKey | None = None) -> None:
        """Move ``tid`` from its node to ``dst`` (LPT-position insert)."""
        if src is None:
            src = self.task_node[tid]
        i = self._remove(src, tid)
        p = _lpt_insert_pos(self.chains.get(dst, []), tid, self.tasks, dst[2])
        self._insert(dst, p, tid)
        self._log.append(("move", tid, src, i, dst, p))
        self._invalidate()

    def apply_swap(self, tk: int, tj: int) -> None:
        """Swap ``tk`` (on I) with ``tj`` (on Iᵃ) — exact edit order of
        phase 3 / §4.3: remove tk, remove tj, insert tk→Iᵃ, insert tj→I."""
        ki = self.task_node[tk]
        ka = self.task_node[tj]
        assert ki != ka, "swap within one node is a no-op"
        i1 = self._remove(ki, tk)
        i2 = self._remove(ka, tj)
        p1 = _lpt_insert_pos(self.chains[ka], tk, self.tasks, ka[2])
        self._insert(ka, p1, tk)
        p2 = _lpt_insert_pos(self.chains[ki], tj, self.tasks, ki[2])
        self._insert(ki, p2, tj)
        self._log.append(("swap", tk, tj, ki, i1, ka, i2, p1, p2))
        self._invalidate()

    def apply_append(self, tid: int, key: NodeKey) -> None:
        """Append ``tid`` at the end of ``key``'s chain (online placement)."""
        self.chains.setdefault(key, [])
        self._insert(key, len(self.chains[key]), tid)
        self._log.append(("append", tid, key))
        self._invalidate()

    def apply_extract(self, tid: int, src: NodeKey | None = None) -> None:
        """Remove ``tid`` from its chain at its current position — the
        outbound half of a *cross-engine* move: the inter-device local
        search extracts a task here and places it on another device's
        engine (each engine only ever sees its own tree)."""
        if src is None:
            src = self.task_node[tid]
        idx = self._remove(src, tid)
        if self._task_node is not None:
            del self._task_node[tid]
        self._log.append(("extract", tid, src, idx))
        self._invalidate()

    def apply_place(self, tid: int, key: NodeKey) -> None:
        """LPT-position insert of a task not currently on any chain — the
        inbound half of a cross-engine move (``self.tasks`` must already
        know ``tid``, bound to this engine's device kind)."""
        p = _lpt_insert_pos(self.chains.get(key, []), tid, self.tasks, key[2])
        self._insert(key, p, tid)
        self._log.append(("place", tid, key, p))
        self._invalidate()

    def apply_retract(self, tid: int, key: NodeKey | None = None) -> None:
        """Retract ``tid`` from the END of its chain — the exact inverse of
        :meth:`apply_append`, for pulling back an appended placement that
        has not started yet (serving re-planning).  Only the last task of a
        chain may be retracted: anything earlier would shift the begin
        times of the tasks behind it, which the no-preemption model
        forbids once they are running."""
        if key is None:
            key = self.task_node[tid]
        lst = self.chains.get(key)
        if not lst or lst[-1] != tid:
            raise ValueError(
                f"task {tid} is not the last task of chain {key}; only a "
                f"chain suffix can be retracted"
            )
        lst.pop()
        self.durs[key].pop()
        self._bump(key)
        if self._task_node is not None:
            del self._task_node[tid]
        self._log.append(("retract", tid, key))
        self._invalidate()

    def apply_stretch(self, tid: int, duration: float) -> None:
        """Override ``tid``'s duration on its chain with runtime truth —
        the closed-loop correction primitive (logged, undo-exact, like
        :meth:`apply_retract`).  ``duration`` is the task's *actual* (or
        projected) runtime; everything behind it on the chain re-times
        through the normal invalidation path.  Stretching (late) and
        shrinking (early completion) are both allowed; the correction
        sticks to the task through later retracts/undos via
        ``self.stretched``.  The no-preemption model is untouched — the
        task still runs once, contiguously, just for a different span."""
        if duration <= 0.0:
            raise ValueError(
                f"stretch duration must be positive, got {duration}"
            )
        key = self.task_node[tid]
        idx = self.chains[key].index(tid)
        old_dur = self.durs[key][idx]
        old_mark = self.stretched.get(tid)
        self.durs[key][idx] = duration
        self.stretched[tid] = duration
        self._bump(key)
        self._log.append(("stretch", tid, key, idx, old_dur, old_mark))
        self._invalidate()

    def apply_cancel(self, tid: int, duration: float) -> None:
        """Cancel ``tid`` mid-run: its chain slot is truncated to
        ``duration`` — the span the slice was physically occupied before
        the cancellation — and marked as a failed occupancy record.  This
        is the speculation primitive: when the first finisher of a
        primary/backup race wins, the loser is cancelled through this
        logged op so successors re-time against the truncated slot and
        ``undo()`` restores the race state bit-exactly.  Like
        :meth:`apply_stretch`, the truncation sticks through later
        retract/undo cycles via ``self.stretched``."""
        if duration <= 0.0:
            raise ValueError(
                f"cancel duration must be positive, got {duration}"
            )
        key = self.task_node[tid]
        idx = self.chains[key].index(tid)
        old_dur = self.durs[key][idx]
        old_mark = self.stretched.get(tid)
        was_cancelled = tid in self.cancelled
        self.durs[key][idx] = duration
        self.stretched[tid] = duration
        self.cancelled.add(tid)
        self._bump(key)
        self._log.append(
            ("cancel", tid, key, idx, old_dur, old_mark, was_cancelled)
        )
        self._invalidate()

    def apply_credit(self, tid: int, credit_s: float) -> None:
        """Shorten ``tid``'s not-yet-started slot by ``credit_s`` seconds
        of banked checkpoint progress — the partial-progress primitive: a
        retried attempt that resumes from its last checkpoint boundary
        occupies only the remainder of its profiled duration.  The credit
        must leave a strictly positive remainder (a fully-credited task
        is a completion, not a placement)."""
        if credit_s <= 0.0:
            raise ValueError(
                f"checkpoint credit must be positive, got {credit_s}"
            )
        key = self.task_node[tid]
        idx = self.chains[key].index(tid)
        old_dur = self.durs[key][idx]
        if credit_s >= old_dur - 1e-12:
            raise ValueError(
                f"checkpoint credit {credit_s} must leave a positive "
                f"remainder of the slot duration {old_dur}"
            )
        old_mark = self.stretched.get(tid)
        remainder = old_dur - credit_s
        self.durs[key][idx] = remainder
        self.stretched[tid] = remainder
        self._bump(key)
        self._log.append(("credit", tid, key, idx, old_dur, old_mark))
        self._invalidate()

    def retract_suffix(self, key: NodeKey, count: int) -> list[int]:
        """Retract the last ``count`` tasks of ``key``'s chain (newest
        first); returns the retracted task ids in retraction order.  Each
        retraction is logged individually, so ``undo()`` restores them one
        at a time."""
        lst = self.chains.get(key, [])
        if count < 0 or count > len(lst):
            raise ValueError(
                f"cannot retract {count} tasks from chain {key} of "
                f"length {len(lst)}"
            )
        out: list[int] = []
        for _ in range(count):
            tid = lst[-1]
            self.apply_retract(tid, key)
            out.append(tid)
        return out

    def undo(self) -> None:
        """Revert the most recent edit exactly."""
        entry = self._log.pop()
        kind = entry[0]
        if kind == "move":
            _, tid, src, i, dst, p = entry
            popped = self.chains[dst].pop(p)
            assert popped == tid
            self.durs[dst].pop(p)
            self._bump(dst)
            self._insert(src, i, tid)
        elif kind == "swap":
            _, tk, tj, ki, i1, ka, i2, p1, p2 = entry
            popped = self.chains[ki].pop(p2)
            assert popped == tj
            self.durs[ki].pop(p2)
            popped = self.chains[ka].pop(p1)
            assert popped == tk
            self.durs[ka].pop(p1)
            self._bump(ki)
            self._bump(ka)
            self._insert(ka, i2, tj)
            self._insert(ki, i1, tk)
        elif kind == "append":
            _, tid, key = entry
            popped = self.chains[key].pop()
            assert popped == tid
            self.durs[key].pop()
            self._bump(key)
            if self._task_node is not None:
                del self._task_node[tid]
        elif kind == "retract":
            _, tid, key = entry
            self._insert(key, len(self.chains[key]), tid)
        elif kind == "stretch":
            _, tid, key, idx, old_dur, old_mark = entry
            self.durs[key][idx] = old_dur
            if old_mark is None:
                self.stretched.pop(tid, None)
            else:
                self.stretched[tid] = old_mark
            self._bump(key)
        elif kind == "cancel":
            _, tid, key, idx, old_dur, old_mark, was_cancelled = entry
            self.durs[key][idx] = old_dur
            if old_mark is None:
                self.stretched.pop(tid, None)
            else:
                self.stretched[tid] = old_mark
            if not was_cancelled:
                self.cancelled.discard(tid)
            self._bump(key)
        elif kind == "credit":
            _, tid, key, idx, old_dur, old_mark = entry
            self.durs[key][idx] = old_dur
            if old_mark is None:
                self.stretched.pop(tid, None)
            else:
                self.stretched[tid] = old_mark
            self._bump(key)
        elif kind == "extract":
            _, tid, src, idx = entry
            self._insert(src, idx, tid)
        elif kind == "place":
            _, tid, key, p = entry
            popped = self.chains[key].pop(p)
            assert popped == tid
            self.durs[key].pop(p)
            self._bump(key)
            if self._task_node is not None:
                del self._task_node[tid]
        else:  # pragma: no cover
            raise AssertionError(f"unknown log entry {kind}")
        self._invalidate()

    def undo_all(self) -> None:
        while self._log:
            self.undo()

    @property
    def log_length(self) -> int:
        """Number of applied (un-undone) edits — a rollback token."""
        return len(self._log)

    def rollback(self, log_length: int) -> None:
        """Undo edits until exactly ``log_length`` remain applied."""
        while len(self._log) > log_length:
            self.undo()

    def chain_version(self, key: NodeKey) -> int:
        """Monotone per-chain edit counter (for caching sorted views)."""
        return self._chain_ver.get(key, 0)

    def chain_durations(self, key: NodeKey) -> Sequence[float]:
        """Read-only view of ``key``'s per-slot durations (stretch
        corrections applied), aligned with ``self.chains[key]`` — the
        public way for cross-engine consumers (the cluster local search)
        to see chain times without reaching into the duration cache."""
        return self.durs.get(key, ())

    def _invalidate(self) -> None:  # overridden by timing subclasses
        pass


@dataclasses.dataclass
class _Eval:
    """One node-granular evaluation of the current chains."""

    node_t0: dict[NodeKey, float]    # chain start (post create/reuse)
    node_end: dict[NodeKey, float]   # chain end (last task end)
    makespan: float
    begin_mass: float | None         # fsum of per-chain begin-time sums;
    #                                  None when mass wasn't requested
    reconfig_end: float              # sequential reconfiguration tail
    order: list[NodeKey] | None      # node processing order (= replay's);
    reconfigs: list[tuple] | None    # None when the fast path skipped the
    #                                  event walk (schedule() re-simulates)


class TimingEngine(ChainState):
    """Incremental, replay-equivalent timing over mutable chains.

    The evaluation context (``release`` / ``alive`` / ``direction`` /
    ``include_reconfig``) is fixed per engine, matching how the consumers
    use replay; ``include_reconfig`` can be overridden per query because
    phase 3 interleaves reconfig-free bookkeeping with full acceptance
    checks on the same state.
    """

    def __init__(
        self,
        assignment: Assignment,
        release: dict | None = None,
        alive: dict[NodeKey, float] | None = None,
        direction: str = "forward",
        include_reconfig: bool = True,
        copy_chains: bool = True,
    ):
        super().__init__(assignment, copy_chains=copy_chains)
        if direction not in ("forward", "reverse"):
            raise ValueError(f"unknown direction {direction!r}")
        self.release = release or {}
        self.alive = dict(alive or {})
        self.direction = direction
        self.include_reconfig = include_reconfig
        spec = self.spec
        # static per-node context, computed once per engine
        if self.release:
            self._node_release: dict[NodeKey, float] = {
                n.key: max(
                    (float(self.release.get(c, 0.0)) for c in n.blocked_cells),
                    default=0.0,
                )
                for n in spec.nodes
            }
        else:
            self._node_release = dict.fromkeys(
                (n.key for n in spec.nodes), 0.0
            )
        # initial per-driver reconfiguration-sequence ends (one per tree,
        # or one global sequence when the spec pins reconfig_scope)
        self._rc_starts = reconfig_sequence_starts(spec, self.release)
        self._alive_sorted = sorted(self.alive)
        self._zero = {s: 0.0 for s in spec.sizes}
        self._ends_template = {
            (r.tree, s): 0.0 for r in spec.roots for s in r.blocked
        }
        self._compute_cells = {
            n.key: n.compute_cells for n in spec.nodes
        }
        self._cache: dict[bool, _Eval] = {}
        # per-chain fold caches: key -> (t0, version, end, begin_mass).  A
        # chain whose start time and contents are unchanged since the last
        # simulation reuses its folded end/mass — this is what makes an
        # edit's re-evaluation touch only the affected nodes' chains (plus
        # the reconfiguration tail, which is always re-walked).  One cache
        # per include_reconfig flag: chain start times differ between the
        # two contexts, and refinement alternates them every iteration.
        self._chain_folds: dict[
            bool, dict[NodeKey, tuple[float, int, float, float]]
        ] = {True: {}, False: {}}
        # begin-time masses are only folded once a consumer asks for them
        # (the seam tie-break does; refinement and phase 2 never do) — the
        # end-only fold is a C-speed ``sum`` instead of a Python loop
        self._need_mass = False

    def _invalidate(self) -> None:
        self._cache.clear()

    # -- accessors ----------------------------------------------------------
    def makespan(self, include_reconfig: bool | None = None) -> float:
        return self._evaluate(include_reconfig).makespan

    def node_end_times(
        self, include_reconfig: bool | None = None
    ) -> dict[NodeKey, float]:
        return self._evaluate(include_reconfig).node_end

    def begin_mass(self, include_reconfig: bool | None = None) -> float:
        ev = self._evaluate(include_reconfig)
        if ev.begin_mass is None:
            self._need_mass = True
            self._cache.clear()
            ev = self._evaluate(include_reconfig)
        return ev.begin_mass

    def slice_end_times(
        self, include_reconfig: bool | None = None
    ) -> dict[tuple[int, int], float]:
        """Last busy time per (tree, slice), == Schedule.slice_end_times()."""
        ev = self._evaluate(include_reconfig)
        ends = dict(self._ends_template)
        cells_of = self._compute_cells
        for key, end in ev.node_end.items():
            for cell in cells_of[key]:
                if end > ends[cell]:
                    ends[cell] = end
        return ends

    def schedule(self, include_reconfig: bool | None = None) -> Schedule:
        """Materialise the full canonical Schedule — bit-identical to
        ``replay()`` of the current chains (items in the same order, same
        reconfiguration windows).  Costs one pass over all tasks; use the
        scalar accessors while searching and this only for the winner."""
        ev = self._eval_recorded(include_reconfig)
        index = self.spec.node_index
        reverse = self.direction == "reverse"
        tasks = self.tasks
        stretched = self.stretched
        items: list[ScheduledTask] = []
        for key in ev.order:
            node = index[key]
            size = key[2]
            t = ev.node_t0[key]
            chain = self.chains[key]
            durs = self.durs[key]
            rng = range(len(chain) - 1, -1, -1) if reverse \
                else range(len(chain))
            for i in rng:
                tid = chain[i]
                if tid in stretched:
                    # runtime-corrected placement: carry the actual end;
                    # a cancelled slot is a failed occupancy record (the
                    # losing attempt of a speculation race)
                    items.append(ScheduledTask(
                        tasks[tid], node, t, size,
                        end_override=t + durs[i],
                        failed=tid in self.cancelled,
                    ))
                else:
                    items.append(ScheduledTask(tasks[tid], node, t, size))
                t += durs[i]
        reconfigs = [
            ReconfigEvent(kind, node, begin, end)
            for kind, node, begin, end in ev.reconfigs
        ]
        return Schedule(spec=self.spec, items=items, reconfigs=reconfigs)

    def task_begin_end(self, tid: int, include_reconfig: bool | None = None
                       ) -> tuple[float, float]:
        """Begin/end of one task, bit-identical to its ScheduledTask."""
        ev = self._evaluate(include_reconfig)
        key = self.task_node[tid]
        chain = self.chains[key]
        durs = self.durs[key]
        order = range(len(chain))
        if self.direction == "reverse":
            order = range(len(chain) - 1, -1, -1)
        t = ev.node_t0[key]
        for i in order:
            if chain[i] == tid:
                return t, t + durs[i]
            t += durs[i]
        raise KeyError(tid)  # pragma: no cover

    # -- core evaluation ----------------------------------------------------
    def _evaluate(self, include_reconfig: bool | None = None) -> _Eval:
        flag = self.include_reconfig if include_reconfig is None \
            else include_reconfig
        ev = self._cache.get(flag)
        if ev is None:
            ev = self._simulate(flag)
            self._cache[flag] = ev
        return ev

    def _eval_recorded(self, include_reconfig: bool | None = None) -> _Eval:
        """Like _evaluate, but guarantees event order/reconfig recording
        (re-simulates if the fast path produced the cached eval)."""
        flag = self.include_reconfig if include_reconfig is None \
            else include_reconfig
        ev = self._cache.get(flag)
        if ev is None or ev.order is None:
            ev = self._simulate(flag, record=True)
            self._cache[flag] = ev
        return ev

    def _simulate_fast(self) -> _Eval:
        """No-reconfig / no-carry-over / forward special case as a plain
        tree walk: with zero-width reconfiguration windows and no release
        constraints, events pop in non-decreasing time, so every chain
        starts exactly at the end of its nearest active ancestor's chain —
        the heap only dictated a summation order, which ``fsum`` makes
        irrelevant.  Scalar accessors are bit-identical to the full walk;
        ``schedule()`` falls back to the recording simulation."""
        chains = self.chains
        durs = self.durs
        chain_fold = self._chain_folds[False]
        chain_ver = self._chain_ver
        need_mass = self._need_mass
        node_t0: dict[NodeKey, float] = {}
        node_end: dict[NodeKey, float] = {}
        masses: list[float] = []
        makespan = 0.0
        stack = [(root, 0.0) for root in self.spec.roots]
        while stack:
            node, t = stack.pop()
            key = node.key
            lst = chains.get(key)
            if lst:
                ver = chain_ver.get(key, 0)
                fold = chain_fold.get(key)
                if fold is not None and fold[0] == t and fold[1] == ver \
                        and (not need_mass or fold[3] is not None):
                    end, mass = fold[2], fold[3]
                elif need_mass:
                    end = t
                    mass = 0.0
                    for d in durs[key]:
                        mass += end
                        end += d
                    chain_fold[key] = (t, ver, end, mass)
                else:
                    end = left_fold(t, durs[key])
                    mass = None
                    chain_fold[key] = (t, ver, end, None)
                node_t0[key] = t
                node_end[key] = end
                if need_mass:
                    masses.append(mass)
                if end > makespan:
                    makespan = end
                t = end
            for child in node.children:
                stack.append((child, t))
        return _Eval(node_t0, node_end, makespan,
                     math.fsum(masses) if need_mass else None,
                     makespan, None, None)

    def _simulate(self, include_reconfig: bool, record: bool = False) -> _Eval:
        """Node-granular mirror of ``repartition.replay`` — same events,
        same heap tie-breaking, same float-addition order."""
        spec = self.spec
        chains = self.chains
        durs = self.durs
        alive = self.alive
        reverse = self.direction == "reverse"
        active = {k for k, v in chains.items() if v}
        t_create = spec.t_create if include_reconfig else self._zero
        t_destroy = spec.t_destroy if include_reconfig else self._zero
        node_release = self._node_release
        index = spec.node_index

        have_alive = bool(alive)
        have_release = bool(self.release)
        if (not include_reconfig and not reverse and not have_alive
                and not have_release and not record):
            return self._simulate_fast()

        need_mass = self._need_mass
        node_t0: dict[NodeKey, float] = {}
        node_end: dict[NodeKey, float] = {}
        masses: list[float] = []
        rc_end = dict(self._rc_starts)  # per-driver sequence ends
        destroyed_alive: set[NodeKey] = set()
        order: list[NodeKey] = []
        reconfigs: list[tuple] = []

        def clear_alive_conflicts(node: InstanceNode) -> None:
            cells = node.blocked_cells
            for akey in self._alive_sorted:
                if akey == node.key or akey in destroyed_alive:
                    continue
                anode = index[akey]
                if not (cells & anode.blocked_cells):
                    continue
                g = anode.tree if anode.tree in rc_end else None
                begin_d = max(rc_end[g], alive[akey])
                rc_end[g] = begin_d + t_destroy[anode.size]
                reconfigs.append(("destroy", anode, begin_d, rc_end[g]))
                destroyed_alive.add(akey)

        chain_fold = self._chain_folds[include_reconfig]
        chain_ver = self._chain_ver

        def run_node(node: InstanceNode, ready: float) -> float:
            key = node.key
            if have_release:
                nr = node_release[key]
                if nr > ready:
                    ready = nr
            if have_alive and key in alive and key not in destroyed_alive:
                t = max(ready, alive[key])
            else:
                if have_alive:
                    clear_alive_conflicts(node)
                g = node.tree if node.tree in rc_end else None
                r = rc_end[g]
                if ready > r:
                    r = ready
                begin_c = r
                r += t_create[node.size]
                rc_end[g] = r
                reconfigs.append(("create", node, begin_c, r))
                t = r
            node_t0[key] = t
            order.append(key)
            ver = chain_ver.get(key, 0)
            fold = chain_fold.get(key)
            if fold is not None and fold[0] == t and fold[1] == ver \
                    and (not need_mass or fold[3] is not None):
                end, mass = fold[2], fold[3]
            else:
                ds = durs[key]
                if reverse:
                    ds = ds[::-1]
                if need_mass:
                    end = t
                    mass = 0.0
                    for d in ds:
                        mass += end
                        end += d
                else:
                    end = left_fold(t, ds)
                    mass = None
                chain_fold[key] = (t, ver, end, mass)
            if need_mass:
                masses.append(mass)
            node_end[key] = end
            return end

        def destroy_node(node: InstanceNode, after: float) -> None:
            g = node.tree if node.tree in rc_end else None
            r = rc_end[g]
            if after > r:
                r = after
            begin_d = r
            r += t_destroy[node.size]
            rc_end[g] = r
            reconfigs.append(("destroy", node, begin_d, r))

        heap: list[tuple[float, int, str, InstanceNode]] = []
        seq = 0

        def push(when: float, what: str, node: InstanceNode) -> None:
            nonlocal seq
            heapq.heappush(heap, (when, seq, what, node))
            seq += 1

        if not reverse:
            # subtree-active flags in one bottom-up pass (spec.nodes is BFS
            # order, so reversed() sees children before parents)
            sub_act: dict[NodeKey, bool] = {}
            for node in reversed(spec.nodes):
                sub_act[node.key] = node.key in active or any(
                    sub_act[c.key] for c in node.children
                )

            heappush = heapq.heappush
            heappop = heapq.heappop
            for root in spec.roots:
                if sub_act[root.key]:
                    heappush(heap, (0.0, seq, "visit", root))
                    seq += 1
            while heap:
                when, _, what, node = heappop(heap)
                if what == "visit":
                    if node.key in active:
                        heappush(heap, (run_node(node, when), seq, "done", node))
                    else:
                        heappush(heap, (when, seq, "done", node))
                    seq += 1
                else:
                    go = False
                    for child in node.children:
                        if sub_act[child.key]:
                            go = True
                            break
                    if not go:
                        continue
                    if node.key in active:
                        destroy_node(node, when)
                    for child in node.children:
                        if sub_act[child.key]:
                            heappush(heap, (when, seq, "visit", child))
                            seq += 1
        else:
            anc: dict[NodeKey, list[NodeKey]] = {k: [] for k in active}
            desc_count: dict[NodeKey, int] = {k: 0 for k in active}

            def walk(node: InstanceNode, chain: list[NodeKey]) -> None:
                if node.key in active:
                    anc[node.key] = list(chain)
                    for a in chain:
                        desc_count[a] += 1
                    chain = chain + [node.key]
                for c in node.children:
                    walk(c, chain)

            for root in spec.roots:
                walk(root, [])

            ready_t: dict[NodeKey, float] = {k: 0.0 for k in active}
            # NodeKey is a tuple of small ints, whose hashing CPython
            # pins across runs (no PYTHONHASHSEED dependence), and the
            # replay reference (repartition.py) seeds its heap from the
            # same literal iteration — sorting here would *break* the
            # bit-identity contract by changing the (time, seq) ties.
            for k in active:  # contracts: ignore[determinism] -- int-tuple set: hash order is run-stable and mirrors replay()'s seq order exactly
                if desc_count[k] == 0:
                    push(0.0, "visit", index[k])
            while heap:
                when, _, what, node = heapq.heappop(heap)
                key = node.key
                if what == "visit":
                    push(run_node(node, when), "done", node)
                else:
                    if anc[key]:
                        destroy_node(node, when)
                    for a in anc[key]:
                        ready_t[a] = max(ready_t[a], when)
                        desc_count[a] -= 1
                        if desc_count[a] == 0:
                            push(ready_t[a], "visit", index[a])

        makespan = max(node_end.values(), default=0.0)
        return _Eval(node_t0, node_end, makespan,
                     math.fsum(masses) if need_mass else None,
                     max(rc_end.values(), default=0.0), order, reconfigs)


def chains_makespan(
    spec: DeviceSpec,
    node_tasks: dict[NodeKey, list[int]],
    node_durs: dict[NodeKey, list[float]],
) -> float:
    """Exact ``replay(assignment).makespan`` for a fresh batch (forward,
    reconfig included, no carry-over state), computed from prebuilt
    duration chains without engine or Schedule construction.  This is the
    phase-2 family-evaluation scorer: one call per candidate allocation.
    Reconfigurations serialise per tree (per driver) like replay's;
    ``reconfig_scope="global"`` specs keep one shared sequence.
    """
    active = {k for k, v in node_tasks.items() if v}
    if not active:
        return 0.0
    t_create = spec.t_create
    t_destroy = spec.t_destroy
    per_tree = spec.reconfig_scope != "global"
    sub_act: dict[NodeKey, bool] = {}
    for node in reversed(spec.nodes):
        sub_act[node.key] = node.key in active or any(
            sub_act[c.key] for c in node.children
        )
    heappush = heapq.heappush
    heappop = heapq.heappop
    heap: list[tuple[float, int, int, InstanceNode]] = []  # 0=visit 1=done
    seq = 0
    rc_end: dict = {}  # per-driver reconfiguration-sequence ends
    makespan = 0.0
    for root in spec.roots:
        if sub_act[root.key]:
            heappush(heap, (0.0, seq, 0, root))
            seq += 1
    while heap:
        when, _, what, node = heappop(heap)
        key = node.key
        g = node.tree if per_tree else None
        if what == 0:
            if key in active:
                r = rc_end.get(g, 0.0)
                if when > r:
                    r = when
                r += t_create[node.size]
                rc_end[g] = r
                t = left_fold(r, node_durs[key])
                if t > makespan:
                    makespan = t
                heappush(heap, (t, seq, 1, node))
            else:
                heappush(heap, (when, seq, 1, node))
            seq += 1
        else:
            go = False
            for child in node.children:
                if sub_act[child.key]:
                    go = True
                    break
            if not go:
                continue
            if key in active:
                r = rc_end.get(g, 0.0)
                if when > r:
                    r = when
                rc_end[g] = r + t_destroy[node.size]
            for child in node.children:
                if sub_act[child.key]:
                    heappush(heap, (when, seq, 0, child))
                    seq += 1
    return makespan


class IdentityCache:
    """Small FIFO cache keyed by an anchor object's identity (plus an
    optional hashable extra), for per-DeviceSpec derived structures.

    ``DeviceSpec`` holds dict fields, so it is not hashable; each entry
    keeps a strong reference to the anchor so its ``id`` stays valid for
    the entry's lifetime.  Shared by the batched-walk matrices below and
    the array-program caches in :mod:`repro.core.family_eval`.

    Why identity keying cannot influence plan bytes (the determinism
    contract): (1) every cached value is a *pure function of the
    anchor's contents* — for a given spec, hit and miss produce the same
    arrays; ``id`` only decides whether the derivation is re-run, never
    what it returns.  (2) The strong reference in the entry pins the
    anchor alive, so an ``id`` can never be recycled onto a different
    live spec while its entry exists — a stale hit is impossible, the
    ``entry[0] is anchor`` guard turns id collisions into ordinary
    misses.  (3) Eviction is FIFO by insertion, not by key order, so
    memory layout never chooses *which* entry survives.  Worst case for
    an unlucky allocation pattern is a recompute, never wrong bytes.
    ``tests/test_timing_engine.py::test_two_engines_same_spec_bit_identical``
    pins the observable half of this argument.
    """

    def __init__(self, max_size: int):
        self._max = max_size
        self._entries: dict[tuple, tuple] = {}

    def get(self, anchor, extra=()):
        entry = self._entries.get((id(anchor), extra))  # contracts: ignore[determinism] -- hit/miss parity: cached value is a pure function of the anchor, strong ref makes stale hits impossible (see class docstring)
        if entry is not None and entry[0] is anchor:
            return entry[1]
        return None

    def put(self, anchor, value, extra=()) -> None:
        if len(self._entries) >= self._max:
            self._entries.pop(next(iter(self._entries)))
        self._entries[(id(anchor), extra)] = (anchor, value)  # contracts: ignore[determinism] -- same argument as get(): identity only gates recomputation, never the computed bytes


#: per-spec static matrices for the batched walk
_BATCH_SPEC_CACHE = IdentityCache(16)


def _batch_spec_arrays(spec: DeviceSpec) -> tuple:
    """(tc, td, childmask, descmask, root_idx, grp_idx, n_groups) per
    spec.nodes order; ``grp_idx`` maps each node to its driver's
    reconfiguration-sequence index (one per tree, or a single shared
    sequence for ``reconfig_scope="global"``)."""
    cached = _BATCH_SPEC_CACHE.get(spec)
    if cached is not None:
        return cached
    import numpy as np

    nodes = spec.nodes
    n = len(nodes)
    index = {node.key: i for i, node in enumerate(nodes)}
    tc = np.array([spec.t_create[node.size] for node in nodes])
    td = np.array([spec.t_destroy[node.size] for node in nodes])
    childmask = np.zeros((n, n), dtype=bool)   # childmask[p, c]: c child of p
    descmask = np.zeros((n, n), dtype=bool)    # descmask[a, b]: b in subtree(a)
    for i, node in enumerate(nodes):
        for child in node.children:
            childmask[i, index[child.key]] = True

    def mark(i: int, anc: list[int]) -> None:
        for a in anc:
            descmask[a, i] = True
        descmask[i, i] = True
        for child in nodes[i].children:
            mark(index[child.key], anc + [i])

    root_idx = [index[r.key] for r in spec.roots]
    for i in root_idx:
        mark(i, [])
    if spec.reconfig_scope != "global":
        trees = sorted({node.tree for node in nodes})
        tmap = {t: k for k, t in enumerate(trees)}
        grp_idx = np.array([tmap[node.tree] for node in nodes])
        n_groups = len(trees)
    else:
        grp_idx = np.zeros(n, dtype=np.int64)
        n_groups = 1
    out = (tc, td, childmask, descmask, root_idx, grp_idx, n_groups)
    _BATCH_SPEC_CACHE.put(spec, out)
    return out


def chains_makespan_batch(spec, chain_durs, chain_len):
    """Batched :func:`chains_makespan` over C candidates at once.

    ``chain_durs`` is a ``(C, N, L)`` float64 array of per-node duration
    chains (N = ``len(spec.nodes)`` in BFS order, rows zero-padded past
    ``chain_len``) and ``chain_len`` the matching ``(C, N)`` counts.
    Returns the ``(C,)`` makespans, **bit-identical** per candidate to
    ``chains_makespan`` on the same chains: the event walk is run in
    lockstep across candidates with the same ``(time, seq)`` heap ordering
    and the chain fold is an ``np.add.accumulate`` — the exact left fold
    the sequential scorer performs.
    """
    import numpy as np

    C, N, L = chain_durs.shape
    (tc_n, td_n, childmask, descmask, root_idx, grp_idx,
     n_groups) = _batch_spec_arrays(spec)
    BIG = np.int64(2**62)
    INF = np.inf

    active = chain_len > 0                               # (C, N)
    if not active.any():
        return np.zeros(C)
    # sub_act[c, a]: any active node in subtree(a); goflag: any sub_act child
    sub_act = (active.astype(np.int8) @ descmask.T.astype(np.int8)) > 0
    goflag = (sub_act.astype(np.int8) @ childmask.T.astype(np.int8)) > 0

    tevt = np.full((C, N), INF)       # pending event time (one per node)
    sevt = np.full((C, N), BIG)       # pending event seq
    wevt = np.zeros((C, N), dtype=np.int8)  # 0 = visit, 1 = done
    seqctr = np.zeros(C, dtype=np.int64)
    for i in root_idx:                # roots pushed in order, seq 0, 1, ...
        pushed = sub_act[:, i]
        tevt[pushed, i] = 0.0
        sevt[pushed, i] = seqctr[pushed]
        seqctr += pushed
    # one reconfiguration sequence per driver group (per tree, or one
    # shared column for reconfig_scope="global" — G=1 reproduces the old
    # globally-coupled walk bit-for-bit)
    re = np.zeros((C, n_groups))
    mk = np.zeros(C)
    r = np.arange(C)

    while True:
        rows = np.isfinite(tevt).any(1)
        if not rows.any():
            break
        when = tevt.min(1)
        cand = tevt == when[:, None]
        seqm = np.where(cand, sevt, BIG)
        sel = cand & (seqm == seqm.min(1)[:, None]) & rows[:, None]
        n_star = sel.argmax(1)
        g_star = grp_idx[n_star]
        re_cur = re[r, g_star]
        what = wevt[r, n_star]
        act = active[r, n_star]
        m_visit = rows & (what == 0)
        m_va = m_visit & act
        m_done = rows & (what == 1)

        # visit of an active node: creation charge + exact chain fold
        t0 = np.maximum(re_cur, when) + tc_n[n_star]
        fold = np.add.accumulate(
            np.concatenate([t0[:, None], chain_durs[r, n_star]], 1), 1
        )
        end = fold[r, chain_len[r, n_star]]
        re[r[m_va], g_star[m_va]] = t0[m_va]
        mk = np.where(m_va & (end > mk), end, mk)
        # visit -> done event in place (active at chain end, else pass-through)
        tevt[r[m_visit], n_star[m_visit]] = np.where(m_va, end, when)[m_visit]
        wevt[r[m_visit], n_star[m_visit]] = 1
        sevt[r[m_visit], n_star[m_visit]] = seqctr[m_visit]
        seqctr += m_visit

        # done: destroy (if active and an active subtree remains) + children
        go = goflag[r, n_star]
        m_dgo = m_done & go
        m_destroy = m_dgo & act
        re_d = np.maximum(re[r, g_star], when) + td_n[n_star]
        re[r[m_destroy], g_star[m_destroy]] = re_d[m_destroy]
        tevt[r[m_done], n_star[m_done]] = INF
        if m_dgo.any():
            push = childmask[n_star] & sub_act & m_dgo[:, None]
            rank = np.cumsum(push, 1) - 1
            tevt = np.where(push, when[:, None], tevt)
            wevt = np.where(push, np.int8(0), wevt)
            sevt = np.where(push, seqctr[:, None] + rank, sevt)
            seqctr += push.sum(1)
    return mk


class ReplayEngine(ChainState):
    """Reference evaluator: same mutable API, every query a full replay.

    Used by the ``use_engine=False`` paths of refinement / seam move-swap
    and by the equivalence tests; intentionally unoptimised.
    """

    def __init__(
        self,
        assignment: Assignment,
        release: dict | None = None,
        alive: dict[NodeKey, float] | None = None,
        direction: str = "forward",
        include_reconfig: bool = True,
        copy_chains: bool = True,
    ):
        super().__init__(assignment, copy_chains=copy_chains)
        self.release = release or {}
        self.alive = dict(alive or {})
        self.direction = direction
        self.include_reconfig = include_reconfig

    def apply_stretch(self, tid: int, duration: float) -> None:
        raise NotImplementedError(
            "ReplayEngine scores every query with a profile-driven "
            "replay(); runtime duration corrections need TimingEngine"
        )

    def apply_cancel(self, tid: int, duration: float) -> None:
        raise NotImplementedError(
            "ReplayEngine scores every query with a profile-driven "
            "replay(); cancelled occupancy records need TimingEngine"
        )

    def apply_credit(self, tid: int, credit_s: float) -> None:
        raise NotImplementedError(
            "ReplayEngine scores every query with a profile-driven "
            "replay(); checkpoint-credit corrections need TimingEngine"
        )

    def _replay(self, include_reconfig: bool | None = None):
        flag = self.include_reconfig if include_reconfig is None \
            else include_reconfig
        return replay(
            self.assignment,
            release=self.release,
            include_reconfig=flag,
            direction=self.direction,
            alive=self.alive,
        )

    def makespan(self, include_reconfig: bool | None = None) -> float:
        return self._replay(include_reconfig).makespan

    def slice_end_times(self, include_reconfig: bool | None = None):
        return self._replay(include_reconfig).slice_end_times()

    def node_end_times(self, include_reconfig: bool | None = None):
        out: dict[NodeKey, float] = {}
        for it in self._replay(include_reconfig).items:
            k = it.node.key
            end = it.end
            if end > out.get(k, float("-inf")):
                out[k] = end
        return out

    def begin_mass(self, include_reconfig: bool | None = None) -> float:
        # per-chain sequential sums (items of one node are contiguous in
        # replay order) combined with the exactly-rounded fsum, so the
        # result is bit-identical to TimingEngine regardless of the order
        # its simulation visited the chains in
        subs: list[float] = []
        sub = 0.0
        cur: NodeKey | None = None
        for it in self._replay(include_reconfig).items:
            k = it.node.key
            if k != cur:
                if cur is not None:
                    subs.append(sub)
                cur, sub = k, 0.0
            sub += it.begin
        if cur is not None:
            subs.append(sub)
        return math.fsum(subs)

    def task_begin_end(self, tid: int, include_reconfig: bool | None = None
                       ) -> tuple[float, float]:
        it = next(
            it for it in self._replay(include_reconfig).items
            if it.task.id == tid
        )
        return it.begin, it.end

    def schedule(self, include_reconfig: bool | None = None) -> Schedule:
        return self._replay(include_reconfig)


def make_engine(
    assignment: Assignment,
    use_engine: bool = True,
    **context,
) -> TimingEngine | ReplayEngine:
    """Factory the consumers use to flip incremental vs reference timing."""
    cls = TimingEngine if use_engine else ReplayEngine
    return cls(assignment, **context)


__all__ = [
    "ChainState",
    "TimingEngine",
    "ReplayEngine",
    "chains_makespan",
    "chains_makespan_batch",
    "left_fold",
    "make_engine",
]
