"""Mean length of the window's prefills (``repro.serve.prefill`` spans),
in ms."""

from bench.lib import spans


def read(run):
    form = run["trace"]
    return spans.mean_ms(form, "repro.serve.prefill") if form else None
