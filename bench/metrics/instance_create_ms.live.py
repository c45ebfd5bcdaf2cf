"""Mean time ``run_live`` took to form an instance's sub-mesh
(``repro.instance.create`` spans), in ms."""

from bench.lib import spans


def read(run):
    form = run["trace"]
    return spans.mean_ms(form, "repro.instance.create") if form else None
