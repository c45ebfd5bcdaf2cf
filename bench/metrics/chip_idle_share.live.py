"""Share of the window's chip-seconds in which no job held the chip, from
each job's start, end and chips as the benchmark's host clock saw them."""

from bench.lib.window import busy_by_chip, idle_share


def read(run):
    share = idle_share(busy_by_chip(run["jobs"]), run["chips"], run["lo"],
                       run["hi"])
    return 100.0 * share
