"""Median length of the window's decode steps (``repro.serve.decode_step``
spans inside their job), in ms."""

from bench.lib import spans


def read(run):
    form = run["trace"]
    return spans.decode_step_ms(form) if form else None
