"""The program's spans in the neutral form: their names, the idle gaps
they label, and the per-job split of the served path, on a hand-made
form whose answers are known, on a slice of a trace recorded on a v5e
chip, and on traces recorded on the CPU."""

import gzip
import json
import re

import pytest

from bench.lib import spans, trace
from bench.lib.window import union_length
from bench.run import _reader
from conftest import ROOT

FIXTURE = ROOT / "bench" / "tests" / "fixtures" / "trace_v5e1_spans.json.gz"
MS = 1_000_000  # ns


def _span(name, start, dur, **stats):
    return [trace.neutral_name(name, stats.items()), start * MS, dur * MS]


def _form():
    """Job A on chip 0, job B on chips 1 and 2, under one run_live."""
    return {
        "window_ns": [0, 100 * MS],
        "devices": {0: [["prefill", 40 * MS, 5 * MS],
                        ["decode", 50 * MS, 2 * MS],
                        ["decode", 60 * MS, 2 * MS],
                        ["after", 97 * MS, 2 * MS]],
                    1: [["prefill", 72 * MS, 8 * MS]]},
        "spans": [
            ["bench.window", 0, 100 * MS],
            ["bench.run_live", 1 * MS, 98 * MS],
            _span("repro.plan", 1, 2, policy="far", tasks=2),
            _span("repro.instance.create", 3, 1, node="T0[0:1]", chips=0),
            _span("repro.task", 4, 61, task=0, node="T0[0:1]", chips=0),
            _span("repro.serve", 5, 59, chips=0, batch=2, prompt=8, gen=3),
            _span("repro.serve.build", 5, 5, chips=0),
            _span("repro.serve.lower", 10, 2, chips=0, program="init"),
            _span("repro.serve.compile", 12, 2, chips=0, program="init"),
            _span("repro.serve.lower", 14, 4, chips=0, program="prefill"),
            _span("repro.serve.compile", 18, 6, chips=0, program="prefill"),
            _span("repro.serve.lower", 24, 3, chips=0, program="decode"),
            _span("repro.serve.compile", 27, 3, chips=0, program="decode"),
            _span("repro.serve.init", 30, 9, chips=0),
            _span("repro.serve.prefill", 39, 7, chips=0),
            _span("repro.serve.decode_step", 49, 4, chips=0, step=0),
            _span("repro.serve.decode_step", 58, 5, chips=0, step=1),
            _span("repro.instance.create", 64, 2, node="T0[1:3]",
                  chips="1 2"),
            _span("repro.serve", 66, 30, chips="1 2", batch=4, prompt=16,
                  gen=2),
            _span("repro.serve.build", 66, 2, chips="1 2"),
            _span("repro.serve.lower", 68, 1, chips="1 2", program="init"),
            _span("repro.serve.compile", 69, 1, chips="1 2",
                  program="init"),
            _span("repro.serve.prefill", 70, 11, chips="1 2"),
            _span("repro.serve.decode_step", 82, 3, chips="1 2", step=0),
        ],
    }


def test_stats_render_into_the_name():
    assert trace.neutral_name(
        "repro.serve", [("chips", "0 1"), ("batch", 8)]
    ) == "repro.serve chips=0,1 batch=8"
    assert trace.neutral_name("repro.serve.build", [("chips", 3)]) == (
        "repro.serve.build chips=3")
    assert spans.chips_of("repro.serve chips=0,1 batch=8") == (0, 1)
    assert spans.chips_of("repro.plan policy=far tasks=2") is None


def test_idle_gaps_name_the_innermost_program_span_on_that_chip():
    gaps = dict((label, s) for label, s in reversed(
        trace.idle_gaps(_form(), [0, 1], k=20)))
    assert gaps["chip 0: repro.serve.compile chips=0 program=prefill"] == (
        pytest.approx(0.040))   # 0-40 ms, its middle under that compile
    assert gaps["chip 1: repro.serve chips=1,2 batch=4 prompt=16 gen=2"] == (
        pytest.approx(0.020))   # 80-100 ms: only job B is about chip 1
    assert gaps["chip 1: bench.run_live"] == pytest.approx(0.072)


def test_jobs_group_their_stages():
    a, b = spans.jobs(_form())
    assert a["chips"] == (0,) and b["chips"] == (1, 2)
    assert a["seconds"] == pytest.approx(0.059)
    assert a["stages"]["lower"] == pytest.approx([0.002, 0.004, 0.003])
    assert a["stages"]["decode_step"] == pytest.approx([0.004, 0.005])
    assert b["stages"]["compile"] == pytest.approx([0.001])
    assert b["stages"]["init"] == []


def test_split_of_the_served_path():
    form = _form()
    assert spans.per_job(form, "build") == pytest.approx((0.005 + 0.002) / 2)
    assert spans.per_job(form, "lower", "compile") == pytest.approx(
        (0.009 + 0.011 + 0.001 + 0.001) / 2)
    assert spans.per_job(form, "init") == pytest.approx(0.009 / 2)
    assert spans.mean_ms(form, "repro.serve.prefill") == pytest.approx(9.0)
    assert spans.mean_ms(form, "repro.plan") == pytest.approx(2.0)
    assert spans.mean_ms(form, "repro.instance.create") == pytest.approx(1.5)
    assert spans.decode_step_ms(form) == pytest.approx(4.0)


def test_a_window_without_jobs_reads_nothing():
    form = {"window_ns": [0, MS], "devices": {}, "spans": []}
    assert spans.jobs(form) == [] and spans.per_job(form, "build") is None
    assert spans.mean_ms(form, "repro.plan") is None
    assert spans.decode_step_ms(form) is None


def _recorded():
    with gzip.open(FIXTURE, "rt") as f:
        form = json.load(f)
    for key in ("devices", "modules"):
        form[key] = {int(k): v for k, v in form[key].items()}
    return form


def test_recorded_chip_trace_keeps_program_spans_and_modules():
    """1.27 s of a traced window of the one-chip cell on a v5e chip: a
    batch's instance formation and its first job, 16x1024 with 32 tokens
    out, as ``extract`` kept it."""
    form = _recorded()
    lo, hi = form["window_ns"]
    assert {spans.base(n) for n, _, _ in form["spans"]} >= {
        "bench.window", "repro.plan", "repro.instance.create", "repro.task",
        "repro.serve", "repro.serve.init", "repro.serve.prefill",
        "repro.serve.decode_step"}
    programs = [m for m in form["modules"][0]
                if re.match(r"jit_(init|prefill_step|decode_step)\(", m[0])]
    assert {m[0].split("(")[0] for m in programs} == {
        "jit_init", "jit_prefill_step", "jit_decode_step"}
    # the operations of the three programs: at least 95% of busy time
    ops = [(max(s, lo), min(s + d, hi)) for _, s, d in form["devices"][0]]
    inside = [(max(a, s), min(b, s + d)) for a, b in ops
              for _, s, d in programs if a < s + d and s < b]
    busy = union_length(ops, lo, hi)
    assert union_length(inside, lo, hi) >= 0.95 * busy > 0
    # every long idle gap is named by a program span on that chip
    assert all(label.startswith("chip 0: repro.")
               for label, _ in trace.idle_gaps(form, [0]))


@pytest.mark.parametrize("metric, value", [
    ("prefill_ms.live", 674.803594),
    ("decode_step_ms.live", 15.237449),
    ("serve_init_s.live", 0.111031228),
    ("instance_create_ms.live", 0.03483),
])
def test_span_readers_on_the_recorded_chip_trace(metric, value):
    assert _reader(metric)({"trace": _recorded()}) == pytest.approx(value)


def test_span_readers_without_a_trace_read_nothing():
    for metric in ("prefill_ms.live", "decode_step_ms.live",
                   "serve_init_s.live", "instance_create_ms.live"):
        assert _reader(metric)({"trace": None}) is None


def test_small_cell_under_the_tracer_reads_every_stage(small_live):
    """The live cell on the CPU under the benchmark's ``Tracer``: the form
    keeps the program's spans, and every span metric finds them (CPU
    seconds, not device numbers).  Set-up built every program, so the
    window's jobs build, lower and compile nothing."""
    import jax

    from bench.run import Tracer
    from bench.runners import live

    tracer = Tracer()
    rec = live.run(*small_live, jax.devices()[:1], 11, 1e-3, tracer)
    form = tracer.form
    assert len(spans.jobs(form)) == rec["attempted"] == 4
    for value in (spans.mean_ms(form, "repro.plan"),
                  spans.mean_ms(form, "repro.instance.create"),
                  spans.per_job(form, "init"),
                  spans.mean_ms(form, "repro.serve.prefill"),
                  spans.decode_step_ms(form)):
        assert value is not None and value > 0
    assert spans.per_job(form, "build", "lower", "compile") == 0
    gens = sorted(j["job"].gen for j in rec["jobs"])
    assert sorted(len(j["stages"]["decode_step"]) + 1
                  for j in spans.jobs(form)) == gens
    for j in spans.jobs(form):
        assert len(j["stages"]["init"]) == len(j["stages"]["prefill"]) == 1
        assert sum(map(sum, j["stages"].values())) <= j["seconds"]


def test_extract_keeps_benchmark_and_program_spans(tmp_path):
    import jax

    from repro.core.spans import span

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with span("repro.serve", chips="0 1", batch=2):
                with span("repro.serve.build", chips="0"):
                    pass
            with jax.profiler.TraceAnnotation("bench.plan"):
                pass
            with jax.profiler.TraceAnnotation("other.span"):
                pass
    finally:
        jax.profiler.stop_trace()
    form = trace.extract(str(tmp_path))
    got = sorted(form["spans"], key=lambda s: s[1])
    assert [s[0] for s in got] == ["bench.window",
                                   "repro.serve chips=0,1 batch=2",
                                   "repro.serve.build chips=0",
                                   "bench.plan"]
    assert form["window_ns"] == [got[0][1], got[0][1] + got[0][2]]
    assert got[1][1] <= got[2][1] and got[2][2] <= got[1][2]
    # the CPU has no TPU planes: no operations and no modules
    assert form["devices"] == form["modules"] == {}
