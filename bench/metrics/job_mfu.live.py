"""Model FLOPs of the jobs that answered in the window, over the window
times the chips times one chip's bf16 peak (bench/peaks.json)."""

from bench.lib.flops import job_flops


def read(run):
    flops = sum(job_flops(run["model"], j["job"].batch, j["job"].prompt,
                          j["job"].gen) for j in run["jobs"])
    peak = run["window_s"] * len(run["chips"]) * run["peak"]["bf16_flops_per_s"]
    return 100.0 * flops / peak
