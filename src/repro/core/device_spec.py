"""Device specifications for MIG-style reconfigurable accelerators.

The paper (§1.2) relies on exactly two structural properties of MIG:

  (P1) instances are organised hierarchically (a *repartitioning tree*:
       an instance is split into disjoint child instances);
  (P2) the valid partitions are precisely the combinations of disjoint
       instances (antichains of the tree that tile the device).

``DeviceSpec`` encodes a device as such a tree (or forest, for multi-GPU /
multi-pod setups, paper §3.2 "multiple A30s"), together with the instance
sizes ``C_G`` and the reconfiguration-cost tables (paper Table 1).

Paper-faithful specs: ``A30``, ``A100``, ``H100``.
TPU-adapted specs (DESIGN.md §2): ``TPU_POD_256`` (8 pod-slices of 32 chips,
full binary tree) and ``TPU_SUPERPOD_512`` (two such pods as a forest);
``V5E_1`` and ``V5E_2X2`` (one chip per slice) for live runs on one v5e host.
"""

from __future__ import annotations

import dataclasses
import itertools
from functools import cached_property
from typing import Mapping, Sequence


@dataclasses.dataclass(frozen=True)
class InstanceNode:
    """One node of a repartitioning tree.

    Attributes:
      tree: index of the tree in the forest (one tree per GPU/pod).
      start: first slice index covered by the *footprint* of this instance.
      size: the instance size in ``C_G`` terms (what ``t_i`` is indexed by —
        the number of slices whose compute the task may use).
      footprint: number of consecutive slices *blocked* by this instance.
        Usually ``== size``; the A100/H100 "3-slice instance on S0..S2 with
        S3's memory" has size 3 but footprint 4 (S3 sits idle but reserved,
        paper §1.2 / §5.2 case 3).
      children: child nodes the instance repartitions into.
    """

    tree: int
    start: int
    size: int
    footprint: int
    children: tuple["InstanceNode", ...] = ()

    # -- identity ----------------------------------------------------------
    @cached_property
    def key(self) -> tuple[int, int, int, int]:
        """Stable identity of the node inside its spec (cached — the
        scheduler hot paths read it millions of times)."""
        return (self.tree, self.start, self.size, self.footprint)

    @property
    def slices(self) -> tuple[int, ...]:
        """Slice indexes whose *compute* the instance uses."""
        return tuple(range(self.start, self.start + self.size))

    @property
    def blocked(self) -> tuple[int, ...]:
        """Slice indexes reserved by the instance (compute + idle)."""
        return tuple(range(self.start, self.start + self.footprint))

    @cached_property
    def blocked_cells(self) -> frozenset[tuple[int, int]]:
        """``{(tree, slice)}`` cells reserved by the instance, precomputed
        once — the conflict/release checks in replay, the timing engine and
        schedule validation are hot enough that rebuilding this set per call
        measurably dominates."""
        return frozenset((self.tree, s) for s in self.blocked)

    @cached_property
    def compute_cells(self) -> tuple[tuple[int, int], ...]:
        """``(tree, slice)`` cells whose *compute* the instance uses."""
        return tuple((self.tree, s) for s in self.slices)

    def __repr__(self) -> str:  # compact, used in schedule dumps
        tag = f"T{self.tree}[{self.start}:{self.start + self.footprint}]"
        if self.footprint != self.size:
            tag += f"(={self.size})"
        return tag


def _binary_tree(tree: int, start: int, size: int) -> InstanceNode:
    """Full binary repartitioning tree over ``size`` slices (power of two)."""
    if size == 1:
        return InstanceNode(tree, start, 1, 1)
    half = size // 2
    return InstanceNode(
        tree, start, size, size,
        children=(_binary_tree(tree, start, half),
                  _binary_tree(tree, start + half, half)),
    )


def _a100_tree(tree: int = 0) -> InstanceNode:
    """A100/H100 repartitioning tree (paper Fig. 4).

    7 -> (4 on S0..S3, 3 on S4..S6)
    the 4 repartitions into the special 3-with-S3-idle instance, which in
    turn repartitions into 2+2 (re-enabling S3); 3 -> 2+1; 2 -> 1+1.
    """
    ones = [InstanceNode(tree, s, 1, 1) for s in range(7)]
    two_01 = InstanceNode(tree, 0, 2, 2, (ones[0], ones[1]))
    two_23 = InstanceNode(tree, 2, 2, 2, (ones[2], ones[3]))
    two_45 = InstanceNode(tree, 4, 2, 2, (ones[4], ones[5]))
    three_idle = InstanceNode(tree, 0, 3, 4, (two_01, two_23))  # S3 idle
    four = InstanceNode(tree, 0, 4, 4, (three_idle,))
    three_r = InstanceNode(tree, 4, 3, 3, (two_45, ones[6]))
    return InstanceNode(tree, 0, 7, 7, (four, three_r))


def _a30_tree(tree: int = 0) -> InstanceNode:
    """A30 repartitioning tree (paper Fig. 4): 4 -> 2+2 -> (1+1)x2."""
    ones = [InstanceNode(tree, s, 1, 1) for s in range(4)]
    two_01 = InstanceNode(tree, 0, 2, 2, (ones[0], ones[1]))
    two_23 = InstanceNode(tree, 2, 2, 2, (ones[2], ones[3]))
    return InstanceNode(tree, 0, 4, 4, (two_01, two_23))


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """A reconfigurable device (or homogeneous group of them).

    Attributes:
      name: e.g. ``"A100"``.
      roots: one repartitioning tree per physical device (paper §3.2 allows a
        forest for multi-GPU; we use it for multi-pod too).
      sizes: the instance sizes ``C_G`` (sorted ascending).
      t_create / t_destroy: reconfiguration cost per instance size, seconds
        (paper Table 1).
      chips_per_slice: TPU adaptation — how many chips one slice stands for
        (1 for the GPU models).
      kind: the instance *type* this device's profiles are keyed by
        (``Profile[(kind, size)]``).  Defaults to ``name``; derived specs
        (``multi_gpu``, ``degrade``, cluster membership) keep the base
        kind so one profile serves every A100 in a fleet, however the
        forest is arranged.
      reconfig_scope: how reconfiguration windows serialise — ``"tree"``
        (per GPU/driver, paper §2.1: each device has its own driver, so
        trees of a forest reconfigure concurrently) or ``"global"`` (the
        pre-fix behaviour that coupled all trees through one sequence;
        kept selectable so the fidelity delta stays measurable).  The
        two are identical on single-tree specs.
    """

    name: str
    roots: tuple[InstanceNode, ...]
    sizes: tuple[int, ...]
    t_create: Mapping[int, float]
    t_destroy: Mapping[int, float]
    chips_per_slice: int = 1
    kind: str = ""
    reconfig_scope: str = "tree"

    @property
    def device_kind(self) -> str:
        """The profile key for this device (``kind``, or ``name``)."""
        return self.kind or self.name

    # -- structure ---------------------------------------------------------
    @cached_property
    def nodes(self) -> tuple[InstanceNode, ...]:
        """All instance nodes, BFS order, roots first."""
        out: list[InstanceNode] = []
        frontier = list(self.roots)
        while frontier:
            node = frontier.pop(0)
            out.append(node)
            frontier.extend(node.children)
        return tuple(out)

    @cached_property
    def n_slices(self) -> int:
        return sum(r.footprint for r in self.roots)

    @cached_property
    def nodes_by_size(self) -> Mapping[int, tuple[InstanceNode, ...]]:
        by: dict[int, list[InstanceNode]] = {s: [] for s in self.sizes}
        for node in self.nodes:
            by[node.size].append(node)
        return {s: tuple(v) for s, v in by.items()}

    @cached_property
    def node_index(self) -> Mapping[tuple[int, int, int, int], InstanceNode]:
        """O(1) node lookup by key (replay and the timing engine resolve
        alive-instance keys on every evaluation)."""
        return {node.key: node for node in self.nodes}

    def node_by_key(self, key: tuple[int, int, int, int]) -> InstanceNode:
        try:
            return self.node_index[key]
        except KeyError:
            raise KeyError(key) from None

    @cached_property
    def valid_partitions(self) -> tuple[tuple[InstanceNode, ...], ...]:
        """Enumerate valid partitions = antichains of disjoint nodes that
        tile each tree (paper Fig. 1: 5 for A30, 19 for A100/H100).

        A node "tiles" its footprint; the special A100 3-instance tiles
        4 slices (S3 idle). Enumerated per tree and combined.
        """

        def tilings(node: InstanceNode) -> list[tuple[InstanceNode, ...]]:
            options: list[tuple[InstanceNode, ...]] = [(node,)]
            if node.children:
                # children of a node partition its footprint between them
                child_opts = [tilings(c) for c in node.children]
                for combo in itertools.product(*child_opts):
                    merged = tuple(itertools.chain.from_iterable(combo))
                    options.append(merged)
            return options

        per_tree = [tilings(r) for r in self.roots]
        out = []
        for combo in itertools.product(*per_tree):
            out.append(tuple(itertools.chain.from_iterable(combo)))
        # dedupe (chains like 4 -> 3' produce the same multiset never; but
        # keep deterministic order)
        seen = set()
        uniq = []
        for p in out:
            k = tuple(sorted(n.key for n in p))
            if k not in seen:
                seen.add(k)
                uniq.append(p)
        return tuple(uniq)

    def is_feasible_instance_set(self, nodes: Sequence[InstanceNode]) -> bool:
        """(P2): any set of pairwise-disjoint tree nodes is a sub-partition."""
        blocked: set[tuple[int, int]] = set()
        node_keys = self.node_index
        for node in nodes:
            if node.key not in node_keys:
                return False
            cells = node.blocked_cells
            if blocked & cells:
                return False
            blocked |= cells
        return True

    # -- fault tolerance (DESIGN.md §8) -------------------------------------
    def degrade(self, dead_slices: Sequence[tuple[int, int]]) -> "DeviceSpec":
        """Return a spec with every instance touching a dead (tree, slice)
        removed — the subtree rooted at the smallest healthy ancestors
        survives. Used by the elastic runtime on node failure."""
        dead = set(dead_slices)

        def prune(node: InstanceNode) -> list[InstanceNode]:
            """Largest healthy subtrees under ``node`` (forest roots)."""
            hit = any((node.tree, s) in dead for s in node.blocked)
            if not hit:
                return [node]
            out: list[InstanceNode] = []
            for child in node.children:
                out.extend(prune(child))
            return out

        new_roots = [n for root in self.roots for n in prune(root)]
        sizes = tuple(sorted({n.size for r in new_roots
                              for n in _iter_nodes(r)}))
        # the reconfiguration tables must shrink with the sizes: a stale
        # entry for a size no longer in the tree would let timing code
        # charge windows for instances that cannot exist
        return dataclasses.replace(
            self,
            name=f"{self.name}-degraded",
            kind=self.device_kind,
            roots=tuple(new_roots),
            sizes=sizes,
            t_create={s: self.t_create[s] for s in sizes},
            t_destroy={s: self.t_destroy[s] for s in sizes},
        )


def _iter_nodes(root: InstanceNode):
    yield root
    for c in root.children:
        yield from _iter_nodes(c)


# ---------------------------------------------------------------------------
# Paper-faithful GPU specs (reconfig times: paper Table 1, seconds)
# ---------------------------------------------------------------------------

A30 = DeviceSpec(
    name="A30",
    roots=(_a30_tree(),),
    sizes=(1, 2, 4),
    t_create={1: 0.11, 2: 0.12, 4: 0.13},
    t_destroy={1: 0.10, 2: 0.10, 4: 0.10},
)

A100 = DeviceSpec(
    name="A100",
    roots=(_a100_tree(),),
    sizes=(1, 2, 3, 4, 7),
    t_create={1: 0.16, 2: 0.17, 3: 0.20, 4: 0.21, 7: 0.24},
    t_destroy={1: 0.20, 2: 0.20, 3: 0.21, 4: 0.21, 7: 0.22},
)

H100 = DeviceSpec(
    name="H100",
    roots=(_a100_tree(),),
    sizes=(1, 2, 3, 4, 7),
    t_create={1: 0.16, 2: 0.21, 3: 0.33, 4: 0.38, 7: 0.42},
    t_destroy={1: 0.21, 2: 0.23, 3: 0.25, 4: 0.26, 7: 0.26},
)


def retree(node: InstanceNode, tree: int) -> InstanceNode:
    """Copy of ``node``'s subtree re-indexed onto forest tree ``tree`` —
    shared by :func:`multi_gpu` and the heterogeneous cluster builder
    (:mod:`repro.core.cluster`), which needs globally-unique tree ids."""
    return InstanceNode(
        tree, node.start, node.size, node.footprint,
        tuple(retree(c, tree) for c in node.children),
    )


def multi_gpu(spec: DeviceSpec, count: int) -> DeviceSpec:
    """Forest of ``count`` identical devices (paper §3.2)."""
    roots = []
    for g in range(count):
        roots.append(retree(spec.roots[0], g))
    return dataclasses.replace(
        spec, name=f"{spec.name}x{count}", kind=spec.device_kind,
        roots=tuple(roots),
    )


# ---------------------------------------------------------------------------
# TPU-adapted specs (DESIGN.md §2): a v5e pod of 256 chips carved into 8
# pod-slices of 32 chips each ((2,16) blocks of the (16,16) mesh).  Instance
# formation cost models sub-mesh (re)formation: barrier + runtime re-init,
# scaled mildly with size (measured MIG times are the GPU analogue; for TPU
# we budget 1-4 s, dominated by coordination, NOT compile — compile caches
# are warm in steady state).
# ---------------------------------------------------------------------------

TPU_POD_256 = DeviceSpec(
    name="TPU_POD_256",
    roots=(_binary_tree(0, 0, 8),),
    sizes=(1, 2, 4, 8),
    t_create={1: 1.0, 2: 1.2, 4: 1.6, 8: 2.4},
    t_destroy={1: 0.5, 2: 0.6, 4: 0.8, 8: 1.2},
    chips_per_slice=32,
)

TPU_SUPERPOD_512 = dataclasses.replace(
    multi_gpu(TPU_POD_256, 2), name="TPU_SUPERPOD_512"
)

# ---------------------------------------------------------------------------
# One TPU v5e host, where live execution runs: a slice is one chip and a
# size-s instance is a sub-mesh of s chips.  ``V5E_1`` is a single chip (one
# instance, nothing to repartition); ``V5E_2X2`` is the four-chip host as a
# binary tree 4 -> 2+2 -> 1+1+1+1.  Formation costs reuse the pod budget
# above; they are assumed, not measured on the host.
# ---------------------------------------------------------------------------

V5E_1 = DeviceSpec(
    name="V5E_1",
    roots=(_binary_tree(0, 0, 1),),
    sizes=(1,),
    t_create={1: 1.0},
    t_destroy={1: 0.5},
)

V5E_2X2 = DeviceSpec(
    name="V5E_2X2",
    roots=(_binary_tree(0, 0, 4),),
    sizes=(1, 2, 4),
    t_create={1: 1.0, 2: 1.2, 4: 1.6},
    t_destroy={1: 0.5, 2: 0.6, 4: 0.8},
)

SPECS: dict[str, DeviceSpec] = {
    "A30": A30,
    "A100": A100,
    "H100": H100,
    "TPU_POD_256": TPU_POD_256,
    "TPU_SUPERPOD_512": TPU_SUPERPOD_512,
    "V5E_1": V5E_1,
    "V5E_2X2": V5E_2X2,
}
