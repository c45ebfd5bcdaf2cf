"""Prompt and served tokens of every job that answered in the window,
over the whole window."""

from bench.lib.window import rate


def read(run):
    return rate(sum(j["job"].tokens for j in run["jobs"]), run["window_s"])
