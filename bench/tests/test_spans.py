"""The program's spans in the neutral form: their names, the idle gaps
they label, and the per-job split of the served path, on a hand-made
form whose answers are known, on a slice of a trace recorded on a v5e
chip, and on a trace recorded on the CPU."""

import json

import pytest

from bench.lib import spans, trace
from conftest import ROOT

FIXTURE = ROOT / "bench" / "tests" / "fixtures" / "trace_v5e1_spans.json"
MS = 1_000_000  # ns


def _span(name, start, dur, **stats):
    return [spans.neutral_name(name, stats.items()), start * MS, dur * MS]


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
    assert spans.neutral_name(
        "repro.serve", [("chips", "0 1"), ("batch", 8)]
    ) == "repro.serve chips=0,1 batch=8"
    assert spans.neutral_name("repro.serve.build", [("chips", 3)]) == (
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


def test_idle_and_busy_under_program_spans():
    form = _form()
    # chip 0 idles 89 ms; the lower and compile spans cover 10-30 ms of it
    assert spans.idle_share_under(
        form, [0], {"repro.serve.lower", "repro.serve.compile"}
    ) == pytest.approx(20 / 89)
    # chip 0 is busy 11 ms, 9 of them inside its job; chip 1's 8 all inside
    assert spans.busy_share_under(form, [0], "repro.serve") == (
        pytest.approx(9 / 11))
    assert spans.busy_share_under(form, [0, 1], "repro.serve") == (
        pytest.approx(17 / 19))


def test_a_window_without_jobs_reads_nothing():
    form = {"window_ns": [0, MS], "devices": {}, "spans": []}
    assert spans.jobs(form) == [] and spans.per_job(form, "build") is None
    assert spans.mean_ms(form, "repro.plan") is None
    assert spans.decode_step_ms(form) is None
    assert spans.idle_share_under(form, [0], {"repro.serve.lower"}) == 0.0


def test_recorded_chip_trace_attributes_its_idle_gap():
    """11.9 s of a traced window on one v5e chip: the end of one job's
    decode, then the next job's stages up to its prefill, with the
    program's spans added to the neutral form."""
    form = json.loads(FIXTURE.read_text())
    form["devices"] = {int(k): v for k, v in form["devices"].items()}
    (long_gap,) = [g for g in trace.idle_gaps(form, [0]) if g[1] > 1.0]
    assert long_gap[0] == "chip 0: repro.serve.compile chips=0 program=init"
    assert spans.busy_share_under(form, [0], "repro.serve") == 1.0
    assert spans.idle_share_under(
        form, [0], {"repro.serve.lower", "repro.serve.compile"}) > 0.99
    assert spans.mean_ms(form, "repro.serve.init") == pytest.approx(
        118.387252)


def test_small_cell_under_the_tracer_reads_every_stage(small_live,
                                                     monkeypatch):
    """The live cell on the CPU under the benchmark's ``Tracer``, with the
    program's spans added to the neutral form: every span metric finds
    its spans (CPU seconds, not device numbers)."""
    import jax

    from bench.run import Tracer
    from bench.runners import live

    real = trace.extract

    def extract(trace_dir):
        form = real(trace_dir)
        form["spans"] += spans.read(trace_dir)
        return form

    monkeypatch.setattr(trace, "extract", extract)
    tracer = Tracer()
    live.run(*small_live, jax.devices()[:1], 11, 0.5, tracer)
    form = tracer.form
    assert len(spans.jobs(form)) == 4
    for value in (spans.mean_ms(form, "repro.plan"),
                  spans.mean_ms(form, "repro.instance.create"),
                  spans.per_job(form, "build"),
                  spans.per_job(form, "lower", "compile"),
                  spans.per_job(form, "init"),
                  spans.mean_ms(form, "repro.serve.prefill"),
                  spans.decode_step_ms(form)):
        assert value is not None and value > 0
    parts = sum(spans.per_job(form, stage) for stage in spans.STAGES)
    whole = sum(j["seconds"] for j in spans.jobs(form)) / 4
    assert 0.95 * whole < parts <= whole


def test_read_a_recorded_trace(tmp_path):
    import jax

    from repro.core.spans import span

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with span("repro.serve", chips="0 1", batch=2):
            with span("repro.serve.build", chips="0"):
                pass
        with jax.profiler.TraceAnnotation("bench.plan"):
            pass
    finally:
        jax.profiler.stop_trace()
    got = sorted(spans.read(str(tmp_path)), key=lambda s: s[1])
    assert [s[0] for s in got] == ["repro.serve chips=0,1 batch=2",
                                   "repro.serve.build chips=0"]
    assert got[0][1] <= got[1][1] and got[1][2] <= got[0][2]
