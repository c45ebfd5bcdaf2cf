"""Smoke run of the scheduler's main path on a TPU, in one process.

    python chip_smoke.py               # one chip: device, plan and live phases
    python chip_smoke.py --four-chips  # four chips: the 2x2 live phase alone

Phases (each one passes or the script exits nonzero):

* device -- jax must find a TPU; on any other platform the script stops.
* plan -- FAR plans a batch of 2000 synthetic tasks on an A100 (paper
  Sec. 6.3 generator) with every family candidate scored by
  ``evaluator="vectorized"`` on the chip, and a ``SchedulingService`` serves
  a 10^4-task Poisson stream with the same evaluator.  Both are compared
  bit for bit with ``evaluator="sequential"`` in this process.  Where the
  chip's float64 is not IEEE, the vectorized evaluator must refuse with
  ``DeviceMismatchError``; the phase then checks the refusal.
* live -- FAR plans a handful of gemma-2b serving jobs (published widths,
  all 18 layers, random weights from the seed) on one v5e chip, and
  ``run_live`` executes them; every job must answer.

``--four-chips`` plans the gemma-2b jobs on the 2x2 host (instances of 1,
2 and 4 chips) and checks the run against the plan: each job ran on the
devices of its instance, disjoint instances overlapped in wall time, and
every instance ran its jobs in the planned order after its ancestors.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import itertools
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench.lib.counters import Counters  # noqa: E402

BATCH_N = 2000          # offline batch size (tasks)
STREAM_N = 10_000       # served stream length (tasks)
ARCH = "gemma-2b"


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


# -- plan -------------------------------------------------------------------


def _timed(counters, fn):
    """(result or the DeviceMismatchError raised, wall s, compile s)."""
    from repro.core.family_eval import DeviceMismatchError

    c0 = counters.now.compile_s
    t0 = time.perf_counter()
    try:
        out = fn()
    except DeviceMismatchError as e:
        out = e
    return out, time.perf_counter() - t0, counters.now.compile_s - c0


def _serve_stream(evaluator: str, seed: int):
    from repro.core.device_spec import A100
    from repro.core.policy import SchedulerConfig
    from repro.core.service import SchedulingService
    from repro.core.traces import TraceSpec, trace_events

    svc = SchedulingService(A100, policy="far", config=SchedulerConfig(
        evaluator=evaluator, max_wait_s=10.0, max_batch=64,
    ))
    for ev in trace_events(A100, TraceSpec(seed=seed, mix="poisson",
                                           n=STREAM_N)):
        svc.poll(ev.arrival)
        svc.submit(ev.task, arrival=ev.arrival)
    schedule = svc.drain()
    decisions = [
        (d.task_id, d.arrival, d.decided_at, d.route, d.flush_id)
        for d in svc.stats.decisions
    ]
    return decisions, schedule, svc.stats.batches


def phase_plan(counters, seed: int) -> None:
    from repro.core.device_spec import A100
    from repro.core.family_eval import DeviceMismatchError
    from repro.core.policy import SchedulerConfig, get_policy
    from repro.core.synth import generate_tasks, workload

    far = get_policy("far")
    tasks = generate_tasks(
        BATCH_N, A100, workload("mixed", "wide", A100), seed=seed
    )
    ref, ref_s, _ = _timed(counters, lambda: far.plan(
        tasks, A100, SchedulerConfig(evaluator="sequential", prune=False)))
    got, got_s, got_c = _timed(counters, lambda: far.plan(
        tasks, A100, SchedulerConfig(evaluator="vectorized", prune=False)))
    rf = ref.extras["far"]
    print(f"plan batch: n={BATCH_N} on A100, family {rf.family_size}, "
          f"sequential evaluate {rf.phase_s['evaluate']:.3f} s "
          f"(plan {ref_s:.3f} s), makespan {ref.makespan!r}")
    batch_refused = isinstance(got, DeviceMismatchError)
    if batch_refused:
        print(f"plan batch: vectorized refused after {got_s:.3f} s "
              f"(compile {got_c:.3f} s): {got}")
    else:
        gf = got.extras["far"]
        print(f"plan batch: vectorized evaluate "
              f"{gf.phase_s['evaluate']:.3f} s (plan {got_s:.3f} s, "
              f"compile {got_c:.3f} s)")
        check(gf.winner_index == rf.winner_index, "batch winner index")
        check(gf.allocation == rf.allocation, "batch allocation")
        check(gf.evaluated == rf.evaluated == rf.family_size,
              "batch scored candidates")
        check(gf.makespan_before_refine == rf.makespan_before_refine,
              "batch pre-refine makespan")
        check(got.makespan == ref.makespan, "batch makespan")
        check(gf.assignment.node_tasks == rf.assignment.node_tasks,
              "batch assignment")
        print(f"plan batch: vectorized == sequential bit for bit "
              f"(winner {gf.winner_index}, {gf.evaluated} candidates)")

    (ref_d, ref_sched, flushes), ref_s, _ = _timed(
        counters, lambda: _serve_stream("sequential", seed))
    print(f"plan stream: {STREAM_N} poisson tasks on A100, {flushes} "
          f"flushes, sequential {ref_s:.3f} s, makespan "
          f"{ref_sched.makespan!r}")
    got, got_s, got_c = _timed(
        counters, lambda: _serve_stream("vectorized", seed))
    stream_refused = isinstance(got, DeviceMismatchError)
    if stream_refused:
        print(f"plan stream: vectorized refused after {got_s:.3f} s "
              f"(compile {got_c:.3f} s): {got}")
    else:
        got_d, got_sched, _ = got
        print(f"plan stream: vectorized {got_s:.3f} s "
              f"(compile {got_c:.3f} s)")
        check(len(got_d) == len(ref_d) == STREAM_N, "stream decision count")
        check(got_d == ref_d, "stream decisions")
        check(got_sched.items == ref_sched.items, "stream schedule")
        print("plan stream: vectorized == sequential bit for bit "
              f"({len(got_d)} decisions)")
    check(batch_refused == stream_refused,
          "the device refused one of the batch and the stream only")


# -- live -------------------------------------------------------------------


def _job_tasks(spec, jobs):
    """One Task per serving job, profiled by the roofline cost model."""
    from repro.configs import get
    from repro.core.costmodel import Job, job_to_task
    from repro.models.config import ShapeConfig

    cfg = get(ARCH)
    return [
        job_to_task(Job(i, cfg, ShapeConfig("serve", prompt + gen, batch,
                                            "decode"), steps=gen), spec)
        for i, (batch, prompt, gen) in enumerate(jobs)
    ]


def _run_jobs(spec, devices, jobs, seed):
    """Plan ``jobs`` with FAR on ``spec`` and execute the plan live."""
    from repro.configs import get
    from repro.core.policy import get_policy
    from repro.launch.serve import serve
    from repro.runtime.live import run_live

    vocab = get(ARCH).vocab_size
    tasks = _job_tasks(spec, jobs)
    plan = get_policy("far").plan(tasks, spec)

    def task_fn(tid, mesh):
        batch, prompt, gen = jobs[tid]
        out = serve(ARCH, batch=batch, prompt_len=prompt, gen=gen,
                    smoke=False, mesh=mesh, seed=seed, log_fn=lambda *_: None)
        tokens = out["tokens"]
        check(tokens.shape == (batch, gen),
              f"job {tid} answered {tokens.shape}, want {(batch, gen)}")
        check(bool(((tokens >= 0) & (tokens < vocab)).all()),
              f"job {tid} answered tokens outside the vocabulary")
        return {"tokens": tokens, "mesh": sorted(
            d.id for d in mesh.devices.flat), **{
            k: out[k] for k in ("prefill_s", "decode_s", "device_ids")}}

    records = run_live(plan.assignment, spec, task_fn, devices=devices)
    check(sorted(r.task_id for r in records) == list(range(len(jobs))),
          "not every job answered")
    planned = {it.task.id: it for it in plan.schedule.items}
    for r in sorted(records, key=lambda r: r.start):
        it = planned[r.task_id]
        print(f"live job {r.task_id} on {r.node} (chips "
              f"{r.payload['device_ids']}): wall {r.end - r.start:.3f} s "
              f"[{r.start:.3f}, {r.end:.3f}] vs planned "
              f"{it.end - it.begin:.3f} s [{it.begin:.3f}, {it.end:.3f}]; "
              f"prefill {r.payload['prefill_s']:.3f} s, decode "
              f"{r.payload['decode_s']:.3f} s")
    return plan, records


def phase_live(seed: int) -> None:
    import jax

    from repro.core.device_spec import V5E_1

    # one prompt for every job: greedy decoding must agree on the prefix
    jobs = [(4, 128, gen) for gen in (8, 16, 24, 32)]
    _, records = _run_jobs(V5E_1, jax.devices()[:1], jobs, seed)
    toks = {r.task_id: r.payload["tokens"] for r in records}
    for a, b in itertools.combinations(sorted(toks), 2):
        k = min(toks[a].shape[1], toks[b].shape[1])
        check(np.array_equal(toks[a][:, :k], toks[b][:, :k]),
              f"jobs {a} and {b} decoded the same prompt differently")
    print(f"live: {len(records)} {ARCH} jobs answered on one chip; greedy "
          f"tokens agree across jobs")


def phase_four_chips(seed: int) -> None:
    import jax

    from repro.core.device_spec import V5E_2X2

    devices = jax.devices()[:4]
    # one long decode, which the cost model speeds up enough on two chips
    # for FAR to give it a 2-chip instance, beside four short jobs
    jobs = [(16, 128, 1024)] + [(4, 128, gen) for gen in (8, 16, 24, 32)]
    plan, records = _run_jobs(V5E_2X2, devices, jobs, seed)
    spec = V5E_2X2
    node_of = {tid: key for key, tids in plan.assignment.node_tasks.items()
               for tid in tids}
    check(max(key[3] for key in node_of.values()) > 1,
          f"FAR put every job on a 1-chip instance: {node_of}")
    by_id = {r.task_id: r for r in records}

    # 1. every job ran on exactly its instance's chips (one per slice)
    for tid, key in node_of.items():
        want = sorted(devices[s].id for s in range(key[1], key[1] + key[3]))
        got = by_id[tid].payload
        check(got["mesh"] == want and got["device_ids"] == want,
              f"job {tid} on {key}: mesh {got['mesh']}, output on "
              f"{got['device_ids']}, want chips {want}")
    print("four chips: every job ran on exactly the chips of its instance")

    # 2. jobs on disjoint instances overlapped in wall time
    overlaps = 0
    for a, b in itertools.combinations(node_of, 2):
        ka, kb = node_of[a], node_of[b]
        if set(range(ka[1], ka[1] + ka[3])) & set(range(kb[1], kb[1] + kb[3])):
            continue
        ra, rb = by_id[a], by_id[b]
        overlaps += ra.start < rb.end and rb.start < ra.end
    check(overlaps > 0, "no two jobs on disjoint instances overlapped")
    print(f"four chips: {overlaps} pairs of jobs on disjoint instances "
          f"overlapped in wall time")

    # 3. each instance ran its chain in the planned order, after every
    #    job of its ancestors (Algorithm 3's order)
    for key, tids in plan.assignment.node_tasks.items():
        ran = sorted(tids, key=lambda t: by_id[t].start)
        check(ran == list(tids), f"instance {key} ran {ran}, planned {tids}")
    index = {node.key: node for node in spec.nodes}

    def descendants(node):
        for c in node.children:
            yield c
            yield from descendants(c)

    for key, tids in plan.assignment.node_tasks.items():
        for d in descendants(index[key]):
            for t in plan.assignment.node_tasks.get(d.key, []):
                check(max(by_id[s].end for s in tids) <= by_id[t].start,
                      f"job {t} on {d.key} started before instance {key} "
                      f"finished")
    print(f"four chips: measured job order matches the plan on "
          f"{len(plan.assignment.node_tasks)} instances")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the live phase on a 2x2 v5e host")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devs = jax.devices()
    platform = devs[0].platform
    check(platform == "tpu",
          f"no TPU: jax found {len(devs)} {platform} device(s) "
          f"({devs[0].device_kind}); this smoke run needs the chip")
    need = 4 if args.four_chips else 1
    check(len(devs) >= need, f"needs {need} TPU chips, found {len(devs)}")
    print(f"device: {len(devs)} x {devs[0].device_kind} ({platform}), "
          f"jax {jax.__version__}")

    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    counters = Counters(jax)

    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(args.seed)
    else:
        phase_plan(counters, args.seed)
        phase_live(args.seed)
    done = counters.now
    print(f"done in {time.perf_counter() - t0:.1f} s; compile "
          f"{done.compile_s:.1f} s; persistent cache {done.hits} "
          f"hits, {done.misses} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
