"""Cells whose jobs run live: a closed backlog of model jobs on one host.

The backlog goes to the scheduler in batches of the deployment's size.
Each batch is planned by the deployment's policy on its device spec
(``get_policy(policy).plan``) and executed by ``runtime.live.run_live``,
which forms one sub-mesh per instance and runs each job there through
``launch.serve.serve``: the system's normal path from plan to tokens.
The next batch starts when the last one has ended.

Set-up plans every batch the run could reach and warms up, on each
instance those plans use, each job shape placed there, with the same
``serve`` call.  The window then runs batches until ``seconds`` have
passed: no batch starts later, and the window ends when the last one
ends.

Once the window has closed, the run is checked:

* every job answered ``(batch, gen)`` token ids inside the vocabulary;
* every job ran on exactly the chips of its instance, and its answer
  lies on them;
* each instance ran its jobs in the planned order, after every job of
  the instances it was cut from;
* a sample of the served sequences, drawn from the seed and always
  holding one of the longest, agrees with the plain float32 reference:
  the mean, over the sample's served positions, of the gap by which the
  served token's reference logit lies below the reference's best stays
  under the deployment's limit.
"""

from __future__ import annotations

import dataclasses
import importlib
import sys
import threading
import time
import traceback

import numpy as np

from bench.lib import traffic as traffic_lib


def _quiet(*_):
    pass


def _nodes(spec):
    stack = list(spec.roots)
    while stack:
        node = stack.pop()
        yield node
        stack.extend(node.children)


def _chips_of(spec, node, devices):
    """The devices a node's instance holds: its slices, in order, over
    ``devices`` split evenly among the spec's slices."""
    per = len(devices) // spec.n_slices
    base = sum(r.footprint for r in spec.roots[: node.tree]) + node.start
    return devices[base * per:(base + node.footprint) * per]


class LiveCell:
    def __init__(self, config: dict, traffic: dict, devices, seed: int):
        from repro.configs import get
        from repro.core.device_spec import SPECS
        from repro.core.policy import get_policy

        self.config, self.traffic, self.devices = config, traffic, devices
        self.seed = seed
        pool = config["pool"]
        self.spec = SPECS[pool["spec"]]
        self.policy = get_policy(pool["policy"])
        self.arch = config["program"]["arch"]
        self.cfg = get(self.arch)
        self.batches = traffic_lib.job_backlog(
            traffic, pool["batch_jobs"], seed)
        self.index = {n.key: n for n in _nodes(self.spec)}
        self.chips = {k: sorted(d.id for d in _chips_of(self.spec, n, devices))
                      for k, n in self.index.items()}
        self.reference = importlib.import_module(
            f"bench.references.{config['reference']}")
        self.jobs: dict[int, dict] = {}
        self.gaps: dict = {}
        self.lock = threading.Lock()

    # -- the system's path ------------------------------------------------

    def tasks(self, batch):
        from repro.core.costmodel import Job, job_to_task
        from repro.models.config import ShapeConfig

        return [job_to_task(Job(j.id, self.cfg, ShapeConfig(
            "serve", j.prompt + j.gen, j.batch, "decode"), steps=j.gen),
            self.spec) for j in batch]

    def serve(self, mesh, batch: int, prompt: int, gen: int, seed: int):
        from repro.launch.serve import serve

        return serve(self.arch, batch=batch, prompt_len=prompt, gen=gen,
                     smoke=False, mesh=mesh, seed=seed, log_fn=_quiet)

    # -- set-up -------------------------------------------------------------

    def warm_up(self) -> None:
        """Each job shape on each instance that a plan of this run puts it
        on, once, through ``run_live`` itself, serving three tokens (two
        decode steps: one fed by the prefill's cache, one by a decode
        step's): every program the window runs is then compiled."""
        import jax

        from repro.runtime.live import run_live

        seen: set = set()
        with jax.profiler.TraceAnnotation("bench.warm_up"):
            for batch in self.batches:
                plan = self.policy.plan(self.tasks(batch), self.spec)
                by_id = {j.id: j for j in batch}
                first = {}
                for key, tids in plan.assignment.node_tasks.items():
                    for t in tids:
                        shape = (key, by_id[t].batch, by_id[t].prompt)
                        if shape not in seen:
                            seen.add(shape)
                            first.setdefault(key, []).append(t)
                if first:
                    warm = dataclasses.replace(plan.assignment,
                                               node_tasks=first)
                    run_live(warm, self.spec, self._warm_fn(by_id),
                             devices=self.devices)

    def _warm_fn(self, by_id):
        def run(tid, mesh):
            job = by_id[tid]
            self.serve(mesh, job.batch, job.prompt, 3, 0)
            return {}

        return run

    # -- window ---------------------------------------------------------------

    def task_fn(self, batch):
        import jax

        by_id = {j.id: j for j in batch}

        def run(tid, mesh):
            job = by_id[tid]
            chips = sorted(d.id for d in mesh.devices.flat)
            label = (f"bench.serve job={tid} "
                     f"chips={','.join(map(str, chips))}")
            with jax.profiler.TraceAnnotation(label):
                start = time.perf_counter()
                out = self.serve(mesh, job.batch, job.prompt, job.gen,
                                 job.seed)
                end = time.perf_counter()
            with self.lock:
                self.jobs[tid] = {
                    "job": job, "start": start, "end": end, "chips": chips,
                    "answer_chips": out["device_ids"],
                    "tokens": np.asarray(out["tokens"]),
                }
            return {}

        return run

    def window(self, seconds: float) -> dict:
        import jax

        from repro.runtime.live import run_live

        plans, plan_s, errors, ran = [], [], [], []
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            for batch in self.batches:
                if time.perf_counter() - t0 >= seconds:
                    break
                with jax.profiler.TraceAnnotation("bench.plan"):
                    tp = time.perf_counter()
                    plan = self.policy.plan(self.tasks(batch), self.spec)
                    plan_s.append(time.perf_counter() - tp)
                plans.append((batch, plan))
                ran.extend(batch)
                with jax.profiler.TraceAnnotation("bench.run_live"):
                    try:
                        run_live(plan.assignment, self.spec,
                                 self.task_fn(batch), devices=self.devices)
                    except Exception:  # the check counts what failed
                        errors.append(traceback.format_exc())
        t1 = time.perf_counter()
        if len(plans) == len(self.batches):
            errors.append("the backlog ran out before the window closed")
        return {"lo": t0, "hi": t1, "plans": plans, "plan_s": plan_s,
                "ran": ran, "errors": errors}

    # -- check ----------------------------------------------------------------

    def check(self, win: dict, control: bool = False):
        """Every compared number beside its limit: ``name -> (value,
        limit)``; a run is correct when no value exceeds its limit.

        With ``control``, also the same checks with the reference's float8
        control in the program's place: the gap of the token it puts first
        at each served position of the same sample.  Returns ``(checks,
        control_checks)`` then.  The gaps compared stay in ``self.gaps``."""
        checks, answered = self.structure(win)
        limit = self.config["limits"]["mean_logit_gap"]
        gaps = self.gaps = (
            self.reference.logit_gaps(self.config, self.sample(answered),
                                      control=control)
            if answered else {"served": None, "control": None})

        def with_gap(g):
            mean = float(g.mean()) if g is not None else float("inf")
            return {**checks, "mean_logit_gap": (mean, limit)}

        if control:
            return with_gap(gaps["served"]), with_gap(gaps["control"])
        return with_gap(gaps["served"])

    def structure(self, win: dict):
        """The checks of answers, placement and order, and the jobs that
        answered."""
        vocab_rows = self.config["weights"]["padded_vocab"]
        answered, bad_answers = [], 0
        for job in win["ran"]:
            got = self.jobs.get(job.id)
            ok = (got is not None
                  and got["tokens"].shape == (job.batch, job.gen)
                  and bool(((got["tokens"] >= 0)
                            & (got["tokens"] < vocab_rows)).all()))
            if ok:
                answered.append(job)
            else:
                bad_answers += 1
        misplaced = misordered = 0
        for batch, plan in win["plans"]:
            tree = plan.assignment.node_tasks
            for key, tids in tree.items():
                want = self.chips[key]
                done = [self.jobs[t] for t in tids if t in self.jobs]
                misplaced += sum(j["chips"] != want
                                 or j["answer_chips"] != want for j in done)
                ran = sorted((t for t in tids if t in self.jobs),
                             key=lambda t: self.jobs[t]["start"])
                misordered += ran != [t for t in tids if t in self.jobs]
                ends = [self.jobs[t]["end"] for t in tids if t in self.jobs]
                for child in _descendants(self.index[key]):
                    for t in tree.get(child.key, ()):
                        if t in self.jobs and ends and \
                                self.jobs[t]["start"] < max(ends):
                            misordered += 1
        return {
            "bad_answers": (bad_answers, 0),
            "failed_batches": (len(win["errors"]), 0),
            "misplaced_jobs": (misplaced, 0),
            "misordered_jobs": (misordered, 0),
        }, answered

    def sample(self, answered) -> list:
        """Served sequences to compare, drawn from the seed: always one of
        the longest, then others up to the traffic's ``sample_rows``."""
        rng = np.random.default_rng((self.seed, 1))
        rows = [(j, r) for j in answered for r in range(j.batch)]
        longest = max(j.prompt + j.gen for j in answered)
        first = [i for i, (j, _) in enumerate(rows)
                 if j.prompt + j.gen == longest]
        pick = [int(rng.choice(first))]
        rest = [i for i in range(len(rows)) if i != pick[0]]
        n = min(self.traffic["sample_rows"], len(rows)) - 1
        pick += [int(i) for i in rng.choice(rest, size=n, replace=False)]
        vocab = self.config["model"]["vocab_size"]
        return [self.reference.Row(
            traffic_lib.prompt_ids(rows[i][0], vocab)[rows[i][1]],
            self.jobs[rows[i][0].id]["tokens"][rows[i][1]]) for i in pick]


def _descendants(node):
    for c in node.children:
        yield c
        yield from _descendants(c)


def run(config, traffic, devices, seed, seconds, tracer) -> dict:
    """One run: set-up, the window (traced by ``tracer``), the check."""
    from bench.lib.counters import Counters
    from bench.lib.device import memory_peak
    import jax

    counters = Counters(jax)
    cell = LiveCell(config, traffic, devices, seed)
    cell.warm_up()
    setup_end = time.perf_counter()
    before = counters.snapshot()
    with tracer:
        win = cell.window(seconds)
    compiled = counters.snapshot() - before
    for name, snap in (("set-up", before), ("window", compiled)):
        print(f"bench: {name}: jax traced {snap.trace_s:.3f} s, lowered "
              f"{snap.lower_s:.3f} s, compiled or loaded {snap.compile_s:.3f} "
              f"s; persistent cache {snap.hits} hits, {snap.misses} misses",
              file=sys.stderr)
    jobs = [cell.jobs[j.id] for j in win["ran"] if j.id in cell.jobs]
    for j in sorted(jobs, key=lambda j: j["start"]):
        job = j["job"]
        print(f"bench: job {job.id} {job.batch}x{job.prompt}+{job.gen} on "
              f"chips {j['chips']}: {j['start'] - win['lo']:.3f} s to "
              f"{j['end'] - win['lo']:.3f} s", file=sys.stderr)
    peak = memory_peak(devices)
    checks = cell.check(win)
    return {
        "setup_end": setup_end, "lo": win["lo"], "hi": win["hi"],
        "attempted": len(win["ran"]),
        "failed": checks["bad_answers"][0],
        "jobs": jobs, "plan_s": win["plan_s"], "compiled": compiled,
        "chips": [d.id for d in devices], "memory_peak_bytes": peak,
        "checks": checks, "errors": win["errors"],
    }
