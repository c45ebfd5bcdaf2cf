"""Mean host time of the scheduler's plan of one batch, in the window."""


def read(run):
    plans = run["plan_s"]
    return 1e3 * sum(plans) / len(plans) if plans else None
