"""Algorithm-3 live executor: real training jobs on sub-device groups,
concurrent across disjoint instances (8 virtual devices, subprocess)."""

import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, sys.argv[1])
import jax
from repro.core.device_spec import A30
from repro.core.problem import Task
from repro.core.far import schedule_batch
from repro.runtime.live import run_live
from repro.launch.train import train

tasks = [
    Task(0, {1: 3.0, 2: 1.7, 4: 1.0}, "jobA"),
    Task(1, {1: 2.0, 2: 1.2, 4: 0.8}, "jobB"),
    Task(2, {1: 1.0, 2: 0.8, 4: 0.7}, "jobC"),
    Task(3, {1: 1.5, 2: 0.9, 4: 0.75}, "jobD"),
]
far = schedule_batch(tasks, A30)
steps = {0: 4, 1: 3, 2: 2, 3: 2}

def task_fn(tid, mesh):
    out = train("gemma-2b", steps=steps[tid], batch=mesh.devices.size,
                seq=32, smoke=True, mesh=mesh, log_every=1000,
                log_fn=lambda *_: None)
    return {"loss": out["last_loss"], "ndev": int(mesh.devices.size)}

recs = run_live(far.assignment, A30, task_fn)
assert len(recs) == 4
assert all(r.payload["loss"] > 0 for r in recs)
# instance sizes follow the FAR molding: devices = 2 * slices (8 devs / 4)
sizes = {r.task_id: r.payload["ndev"] for r in recs}
by_node = far.assignment.node_tasks
for key, tids in by_node.items():
    for tid in tids:
        assert sizes[tid] == 2 * key[2], (tid, sizes[tid], key)
# tasks on disjoint instances overlap in wall time (concurrency check):
# find two placements on disjoint nodes and assert their spans intersect
import itertools
spans = {r.task_id: (r.start, r.end) for r in recs}
nodes = {tid: key for key, tids in by_node.items() for tid in tids}
overlap = False
for a, b in itertools.combinations(spans, 2):
    ka, kb = nodes[a], nodes[b]
    cells_a = set(range(ka[1], ka[1] + ka[3]))
    cells_b = set(range(kb[1], kb[1] + kb[3]))
    if cells_a & cells_b:
        continue
    (s1, e1), (s2, e2) = spans[a], spans[b]
    if s1 < e2 and s2 < e1:
        overlap = True
assert overlap, "disjoint instances never ran concurrently"
print("LIVE_OK")
"""


@pytest.mark.parametrize("spec_name,n_devices", [
    ("A100", 4), ("A30", 6), ("TPU_POD_256", 4), ("V5E_2X2", 0),
])
def test_live_executor_refuses_unmappable_devices(spec_name, n_devices):
    """Each slice needs the same whole number of devices; nothing runs
    when the spec's slices do not divide the device list."""
    from repro.core.device_spec import SPECS
    from repro.core.repartition import Assignment
    from repro.runtime.live import run_live

    spec = SPECS[spec_name]
    ran = []
    with pytest.raises(ValueError, match="do not map onto"):
        run_live(Assignment(spec, {}, {}), spec,
                 lambda tid, mesh: ran.append(tid),
                 devices=[object()] * n_devices)
    assert ran == []


def test_live_executor_runs_far_tree_concurrently():
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT, "src"],
        capture_output=True, text=True, timeout=900, cwd=".",
    )
    assert "LIVE_OK" in out.stdout, out.stdout[-1500:] + out.stderr[-3000:]
