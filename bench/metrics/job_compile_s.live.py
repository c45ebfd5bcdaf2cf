"""Seconds per job, in the window, that jax spent tracing, lowering and
compiling or loading compiled programs (jax.monitoring durations)."""


def read(run):
    jobs = len(run["jobs"])
    return run["compiled"].total_s / jobs if jobs else None
