"""Live executor — the paper's Algorithm 3 on real devices.

Walks the FAR repartitioning tree exactly as the paper's GPU runner does:
each node with tasks "creates" its instance (here: builds a JAX mesh over
the node's device group), runs its tasks sequentially on it, "destroys"
it, and recurses into its children in separate threads, so tasks on
disjoint instances run concurrently.  Wall-clock task start/end offsets
are reported for the Table-3-style sim-vs-real comparison.

Tasks here are real work: a model job on the instance's devices.  One
slice maps to ``len(devices) // n_slices`` consecutive devices, so the
device count must be a multiple of the spec's slice count; any other
count is refused rather than leaving instances without devices.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable

import jax

from repro.core.device_spec import DeviceSpec, InstanceNode
from repro.core.repartition import Assignment
from repro.core.spans import chip_ids, span
from repro.launch.mesh import make_submesh


@dataclasses.dataclass
class LiveRecord:
    task_id: int
    node: str
    start: float
    end: float
    payload: dict


def run_live(
    assignment: Assignment,
    spec: DeviceSpec,
    task_fn: Callable[[int, object], dict],
    devices=None,
) -> list[LiveRecord]:
    """Execute an assignment on real devices (Algorithm 3).

    Args:
      assignment: FAR output tree (task lists per instance node).
      spec: the device spec the assignment was built for.
      task_fn: ``task_fn(task_id, mesh) -> payload dict`` — the actual work.
      devices: flat device list (default: all jax.devices()).

    Raises:
      ValueError: the spec's slices do not map onto ``devices`` (their
        count is not a positive multiple of ``spec.n_slices``).
      The first exception a task raised, once every other instance has
        finished; the failed instance's subtree does not run.
    """
    devices = list(devices if devices is not None else jax.devices())
    if not devices or len(devices) % spec.n_slices:
        raise ValueError(
            f"{spec.name}'s {spec.n_slices} slices do not map onto "
            f"{len(devices)} devices: each slice needs the same whole "
            f"number of devices"
        )
    per_slice = len(devices) // spec.n_slices
    records: list[LiveRecord] = []
    errors: list[BaseException] = []
    lock = threading.Lock()
    init_time = time.perf_counter()

    def devices_of(node: InstanceNode):
        base = (
            sum(r.footprint for r in spec.roots[: node.tree]) + node.start
        )
        lo = base * per_slice
        hi = (base + node.footprint) * per_slice
        return devices[lo:hi]

    def execute_tree(node: InstanceNode) -> None:
        tids = assignment.node_tasks.get(node.key, [])
        try:
            if tids:
                devs = devices_of(node)
                chips = chip_ids(devs)
                with span("repro.instance.create", node=repr(node),
                          chips=chips):
                    mesh = make_submesh(devs, data=len(devs), model=1)
                for tid in tids:
                    t0 = time.perf_counter() - init_time
                    with span("repro.task", task=tid, node=repr(node),
                              chips=chips):
                        payload = task_fn(tid, mesh)
                    t1 = time.perf_counter() - init_time
                    with lock:
                        records.append(LiveRecord(
                            tid, repr(node), t0, t1, payload
                        ))
        except BaseException as e:  # re-raised by run_live after the joins
            with lock:
                errors.append(e)
            return  # a failed instance is not repartitioned
        run_all(node.children)

    def run_all(nodes) -> None:
        threads = [
            threading.Thread(target=execute_tree, args=(node,))
            for node in nodes
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    run_all(spec.roots)
    if errors:
        raise errors[0]
    records.sort(key=lambda r: r.end)
    return records
