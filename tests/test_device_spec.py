"""Device-spec structure: valid partitions, trees, degradation."""

import pytest

from repro.core.device_spec import (
    A30, A100, H100, TPU_POD_256, TPU_SUPERPOD_512, V5E_1, V5E_2X2, multi_gpu,
)


def test_v5e_host_specs():
    """One chip per slice: a single-chip instance, and the 2x2 host as
    4 -> 2+2 -> 1+1+1+1."""
    assert V5E_1.n_slices == 1 and V5E_1.sizes == (1,)
    assert len(V5E_1.valid_partitions) == 1
    assert V5E_2X2.n_slices == 4 and V5E_2X2.sizes == (1, 2, 4)
    assert sorted(n.size for n in V5E_2X2.nodes) == [1, 1, 1, 1, 2, 2, 4]
    assert len(V5E_2X2.valid_partitions) == 5


def test_partition_counts_match_paper_fig1():
    assert len(A30.valid_partitions) == 5
    assert len(A100.valid_partitions) == 19
    assert len(H100.valid_partitions) == 19


def test_partitions_tile_all_slices():
    for spec in (A30, A100, TPU_POD_256):
        for p in spec.valid_partitions:
            blocked = sorted(
                (node.tree, s) for node in p for s in node.blocked
            )
            want = sorted(
                (r.tree, s) for r in spec.roots for s in r.blocked
            )
            assert blocked == want, (spec.name, p)


def test_a100_has_no_2_4_1_style_invalid_partition():
    # paper §2.3: 2-4-1 with the 4 in the middle is NOT a valid partition
    for p in A100.valid_partitions:
        sizes_at = sorted((n.start, n.size) for n in p)
        assert (2, 4) not in sizes_at  # no 4-slice instance starting at S2


def test_a100_special_three_instance_blocks_s3():
    threes = [n for n in A100.nodes if n.size == 3]
    assert len(threes) == 2
    left = next(n for n in threes if n.start == 0)
    assert left.footprint == 4  # S3 reserved-idle
    right = next(n for n in threes if n.start == 4)
    assert right.footprint == 3


def test_disjoint_node_sets_are_feasible():
    by_key = {(n.start, n.size): n for n in A100.nodes
              if n.footprint == n.size}
    four = next(n for n in A100.nodes if n.size == 4)
    combo = [four, by_key[(4, 2)], by_key[(6, 1)]]  # 4 + (4,2) + (6,1)
    assert A100.is_feasible_instance_set(combo)
    seven = next(n for n in A100.nodes if n.size == 7)
    bad = [seven, by_key[(0, 1)]]  # overlapping footprints
    assert not A100.is_feasible_instance_set(bad)


def test_multi_gpu_forest():
    spec = multi_gpu(A30, 3)
    assert spec.n_slices == 12
    assert len(spec.roots) == 3
    assert len(spec.valid_partitions) == 5 ** 3


def test_superpod_is_two_pods():
    assert TPU_SUPERPOD_512.n_slices == 16
    assert len(TPU_SUPERPOD_512.roots) == 2


@pytest.mark.parametrize("dead,expect_slices", [
    ([(0, 0)], 7), ([(0, 0), (0, 7)], 6), ([(0, 3)], 7),
])
def test_degrade_removes_only_affected_subtrees(dead, expect_slices):
    d = TPU_POD_256.degrade(dead)
    assert d.n_slices == expect_slices
    for r in d.roots:
        for s in r.blocked:
            assert (r.tree, s) not in set(dead)
    # sizes remain schedulable subset
    assert set(d.sizes) <= set(TPU_POD_256.sizes)


def test_degrade_keeps_t_tables():
    d = A100.degrade([(0, 6)])
    for s in d.sizes:
        assert s in d.t_create and s in d.t_destroy


def test_degrade_drops_stale_reconfig_table_entries():
    """The tables shrink with the sizes: no create/destroy cost may
    survive for an instance size the degraded tree can no longer form."""
    d = A30.degrade([(0, 0)])  # kills the 4 and the left 2
    assert set(d.sizes) == {1, 2}
    assert set(d.t_create) == set(d.sizes)
    assert set(d.t_destroy) == set(d.sizes)
    assert d.device_kind == "A30"  # kind survives renaming


def test_degrade_to_empty_forest():
    dead = [(0, s) for s in range(4)]
    d = A30.degrade(dead)
    assert d.roots == ()
    assert d.sizes == ()
    assert d.t_create == {} and d.t_destroy == {}
    assert d.n_slices == 0


def test_degrade_a100_footprint4_three_instance():
    """Killing S3 removes the special 3-with-S3-idle instance (footprint
    4) along with the 4 and the root, leaving 2(S0,S1), 1(S2) and the
    right-hand 3 — and the tables shrink to the surviving sizes."""
    d = A100.degrade([(0, 3)])
    assert not any(n.footprint != n.size for n in d.nodes)  # the 3' is gone
    roots = sorted((r.start, r.size) for r in d.roots)
    assert roots == [(0, 2), (2, 1), (4, 3)]
    assert set(d.sizes) == {1, 2, 3}
    assert set(d.t_create) == {1, 2, 3}


def test_degrade_inside_cluster():
    from repro.core.cluster import cluster

    cs = cluster(A30, A100)
    a100_tree = cs.devices[1].roots[0].tree
    d1 = cs.degrade([(a100_tree, 3)])
    assert len(d1.devices) == 2
    assert d1.devices[0].sizes == A30.sizes          # untouched device
    assert set(d1.devices[1].sizes) == {1, 2, 3}     # degraded A100
    assert d1.devices[1].device_kind == "A100"
    # tree ids keep their global identity through degradation
    assert {r.tree for r in d1.devices[1].roots} == {a100_tree}
    # killing every A30 slice drops the device from the pool
    a30_tree = cs.devices[0].roots[0].tree
    d2 = cs.degrade([(a30_tree, s) for s in range(4)])
    assert len(d2.devices) == 1
    assert d2.devices[0].device_kind == "A100"
