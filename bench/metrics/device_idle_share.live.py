"""Share of the traced window in which no device operation ran, averaged
over the cell's chips (union of each chip's XLA Ops)."""

from bench.lib import trace


def read(run):
    form = run["trace"]
    if form is None:
        return None
    busy = trace.busy_seconds(form)
    window = trace.window_seconds(form)
    chips = run["chips"]
    return 100.0 * (1.0 - sum(busy.get(c, 0.0) for c in chips)
                    / (window * len(chips)))
