"""Model FLOPs of the jobs that answered in the window, counted by the
configuration's reference (``job_flops`` in ``bench/references/``), over
the window times the chips times one chip's bf16 peak (bench/peaks.json)."""

import importlib


def read(run):
    job_flops = importlib.import_module(
        f"bench.references.{run['reference']}").job_flops
    flops = sum(job_flops(run["model"], j["job"].batch, j["job"].prompt,
                          j["job"].gen) for j in run["jobs"])
    peak = run["window_s"] * len(run["chips"]) * run["peak"]["bf16_flops_per_s"]
    return 100.0 * flops / peak
