"""Runtime: cost model, executor, fault tolerance, elastic rescheduling."""

import itertools

import pytest

from repro.configs import ARCHS
from repro.core.costmodel import Job, job_time, job_to_task, step_time
from repro.core.device_spec import TPU_POD_256
from repro.core.problem import validate_schedule
from repro.models.config import SHAPES
from repro.runtime import ClusterManager, Fault, SimExecutor, Slowdown


def _jobs(mgr, n=10, steps=50):
    shapes = [SHAPES["train_4k"], SHAPES["decode_32k"],
              SHAPES["prefill_32k"]]
    for cfg, sh in itertools.islice(
        itertools.product(ARCHS.values(), shapes), n
    ):
        mgr.submit(mgr.new_job(cfg, sh, steps=steps))


def test_cost_model_times_monotone_non_increasing():
    for cfg in ARCHS.values():
        for sh in ("train_4k", "prefill_32k", "decode_32k"):
            job = Job(0, cfg, SHAPES[sh], steps=10)
            task = job_to_task(job, TPU_POD_256)
            sizes = sorted(task.times)
            assert task.check_time_monotone(), (cfg.name, sh)
            assert all(task.times[s] > 0 for s in sizes)


@pytest.mark.parametrize("chips", [1, 2, 4, 8, 16])
def test_cost_model_below_one_model_axis(chips):
    """On a 1-16 chip instance the model axis shrinks to the chips there
    are and data parallelism stays 1: times are finite, and more chips
    are never slower than one."""
    cfg = ARCHS["gemma-2b"]
    for sh in ("train_4k", "decode_32k"):
        t = step_time(cfg, SHAPES[sh], 1, chips_per_slice=chips)
        assert 0 < t < float("inf"), (chips, sh)
        if chips > 1:
            assert t <= step_time(cfg, SHAPES[sh], 1, chips_per_slice=1)


def test_cost_model_profiles_v5e_host():
    from repro.core.device_spec import V5E_2X2

    job = Job(0, ARCHS["gemma-2b"], SHAPES["decode_32k"], steps=10)
    task = job_to_task(job, V5E_2X2)
    assert sorted(task.times) == [1, 2, 4]
    assert task.check_time_monotone()


def test_cost_model_spill_makes_work_non_monotone():
    """qwen1.5-110b training cannot fit 32 chips -> super-linear speedup
    regime (the TPU analogue of paper §2.4)."""
    cfg = ARCHS["qwen1.5-110b"]
    job = Job(0, cfg, SHAPES["train_4k"], steps=10)
    t = job_to_task(job, TPU_POD_256)
    works = {s: s * t.times[s] for s in t.times}
    assert min(works, key=works.get) > 1  # min-work NOT at one slice


def test_executor_zero_drift_without_faults():
    mgr = ClusterManager(TPU_POD_256)
    _jobs(mgr, 8)
    rec = mgr.run_batch()
    assert rec.result.drift == pytest.approx(0.0, abs=1e-9)
    assert len(rec.result.finished) == 8
    validate_schedule(rec.schedule, check_reconfig=False)


def test_executor_detects_stragglers():
    mgr = ClusterManager(TPU_POD_256, straggle_tol=0.05)
    _jobs(mgr, 8)
    rec = mgr.run_batch(slowdowns=[Slowdown(0, 0, 1.2)])
    assert rec.result.stragglers  # something ran on slice 0 and drifted
    assert rec.result.makespan >= rec.result.sim_makespan


def test_fault_kills_and_restarts_from_checkpoint():
    mgr = ClusterManager(TPU_POD_256)
    _jobs(mgr, 10, steps=100)
    first = mgr.run_batch()
    mid = first.result.makespan  # schedule a fresh batch with a mid-fault
    _jobs(mgr, 10, steps=100)
    rec = mgr.run_batch(faults=[Fault(mid + 50.0, 0, 3)])
    assert rec.result.killed
    # killed jobs requeued with remaining steps <= original
    restarts = [j for j in mgr.queue if "restart" in (j.name or "")]
    assert len(restarts) == len(rec.result.killed)
    for j in restarts:
        assert 0 < j.steps <= 100
    # degraded spec excludes the dead slice
    assert mgr.spec.n_slices == 7
    # next batch completes on the degraded pod
    rec2 = mgr.run_batch()
    assert len(rec2.result.finished) == len(rec2.jobs)
    validate_schedule(rec2.schedule, check_reconfig=False)


def test_utilization_reported():
    mgr = ClusterManager(TPU_POD_256)
    _jobs(mgr, 12)
    mgr.run_batch()
    u = mgr.utilization()
    assert 0.2 < u <= 1.0


def test_job_time_decreases_with_slices():
    cfg = ARCHS["gemma3-12b"]
    job = Job(0, cfg, SHAPES["train_4k"], steps=100)
    times = [job_time(job, s) for s in (1, 2, 4, 8)]
    assert times == sorted(times, reverse=True)


def test_multibatch_cluster_keeps_validating():
    mgr = ClusterManager(TPU_POD_256, concat_mode="auto")
    for _ in range(3):
        _jobs(mgr, 6)
        mgr.run_batch()
    combined_items = [
        it for r in mgr.history for it in r.schedule.items
    ]
    assert len(combined_items) == 18
    # every pair of overlapping-footprint items is time-disjoint
    for i, a in enumerate(combined_items):
        ca = {(a.node.tree, s) for s in a.node.blocked}
        for b in combined_items[i + 1:]:
            cb = {(b.node.tree, s) for s in b.node.blocked}
            if ca & cb:
                assert a.end <= b.begin + 1e-6 or b.end <= a.begin + 1e-6
