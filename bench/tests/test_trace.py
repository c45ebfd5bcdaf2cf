"""The reduction from a profiler trace to the device numbers, on a small
recorded trace (the neutral form ``trace.extract`` keeps) and on a
hand-made one whose answers are known."""

import json

import pytest

from bench.lib import trace
from conftest import ROOT

FIXTURE = ROOT / "bench" / "tests" / "fixtures" / "trace_v5e1.json"

MS = 1_000_000  # ns


def _form():
    return {
        "window_ns": [0, 100 * MS],
        "devices": {
            0: [["fusion.1", 10 * MS, 20 * MS], ["dot.2", 30 * MS, 5 * MS],
                ["fusion.1", 90 * MS, 20 * MS],
                ["while.3", 10 * MS, 25 * MS]],   # holds the first two
            1: [["dot.2", -5 * MS, 10 * MS]],
        },
        "spans": [["bench.window", 0, 100 * MS],
                  ["bench.run_live", 1 * MS, 98 * MS],
                  ["bench.serve job=3 chips=0", 5 * MS, 60 * MS],
                  ["bench.serve job=4 chips=1", 5 * MS, 30 * MS]],
    }


def test_busy_is_the_union_of_operations_inside_the_window():
    busy = trace.busy_seconds(_form())
    assert busy[0] == pytest.approx(0.035)   # 10-35 ms and 90-100 ms
    assert busy[1] == pytest.approx(0.005)   # clipped at the window's start
    assert trace.window_seconds(_form()) == pytest.approx(0.1)


def test_top_ops_average_over_the_chips():
    ops = trace.top_ops(_form(), [0, 1])
    assert ops[0][0] == "fusion.1"
    assert ops[0][1] == pytest.approx((0.020 + 0.010) / 2)
    assert ops[1] == ["dot.2", pytest.approx((0.005 + 0.005) / 2)]
    assert "while.3" not in [name for name, _ in ops]


def test_idle_gaps_name_what_the_host_did_for_that_chip():
    gaps = trace.idle_gaps(_form(), [0, 1], k=3)
    assert gaps[0] == ["chip 1: bench.run_live", pytest.approx(0.095)]
    assert gaps[1] == ["chip 0: bench.serve job=3 chips=0",
                       pytest.approx(0.055)]
    assert gaps[2] == ["chip 0: bench.serve job=3 chips=0",
                       pytest.approx(0.010)]


def test_recorded_trace():
    form = json.loads(FIXTURE.read_text())
    form["devices"] = {int(k): v for k, v in form["devices"].items()}
    busy = trace.busy_seconds(form)
    window = trace.window_seconds(form)
    assert 0 < busy[0] < window
    ops = trace.top_ops(form, [0])
    assert len(ops) == 10
    assert sum(s for _, s in ops) <= busy[0] * (1 + 1e-9)
    gaps = trace.idle_gaps(form, [0])
    assert len(gaps) == 10 and all(g[0].startswith("chip 0: ") for g in gaps)
    assert busy[0] + sum(s for _, s in gaps) <= window * (1 + 1e-9)
