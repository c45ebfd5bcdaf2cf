"""Seconds per job that ``serve()`` spent making its weights on the chips
(``repro.serve.init`` spans), over the window's jobs."""

from bench.lib import spans


def read(run):
    form = run["trace"]
    return spans.per_job(form, "init") if form else None
