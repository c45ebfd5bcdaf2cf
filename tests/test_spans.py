"""Program spans on the profiler's clock: a plan and a served job run
through ``run_live`` under ``jax.profiler`` on the CPU, read back from the
``.xplane.pb``, must form the tree of the served path, name the chips of
the job's mesh, and leave the served tokens those of a plain greedy
prefill and decode."""

import glob
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")
ARCH = "qwen2.5-3b"
BATCH, PROMPT, GEN, SEED = 2, 8, 4, 5


def _spans(trace_dir):
    """Every ``repro.`` span of the trace: ``(name, stats, start, end,
    line)``, ``line`` naming the host thread that recorded it."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    out = []
    for plane in ProfileData.from_file(path[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out.extend(
                (e.name, dict(e.stats), e.start_ns,
                 e.start_ns + e.duration_ns, (plane.name, i))
                for e in line.events if e.name.startswith("repro."))
    return out


def _parent(spans, child):
    """The shortest other span on the child's thread that holds it."""
    name, _, start, end, line = child
    holders = [s for s in spans if s is not child and s[4] == line
               and s[2] <= start and end <= s[3]]
    return min(holders, key=lambda s: s[3] - s[2], default=None)


@pytest.fixture(scope="module")
def traced_job(tmp_path_factory):
    """One FAR plan of two jobs on ``V5E_1`` and one of them served live,
    under the profiler with its Python tracer off, from an empty store of
    compiled programs: the job builds all three."""
    import jax

    from repro.core.device_spec import V5E_1
    from repro.core.policy import get_policy
    from repro.core.problem import Task
    from repro.launch.serve import clear_programs, serve
    from repro.runtime.live import run_live

    out = {}

    def task_fn(tid, mesh):
        out["mesh_ids"] = sorted(d.id for d in mesh.devices.flat)
        out["served"] = serve(ARCH, batch=BATCH, prompt_len=PROMPT, gen=GEN,
                              smoke=True, mesh=mesh, seed=SEED,
                              log_fn=lambda *_: None)
        return {}

    clear_programs()
    trace_dir = tmp_path_factory.mktemp("trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        plan = get_policy("far").plan(
            [Task(0, {1: 1.0}), Task(1, {1: 2.0})], V5E_1)
        one = {k: v[:1] for k, v in plan.assignment.node_tasks.items()}
        plan.assignment.node_tasks = one
        run_live(plan.assignment, V5E_1, task_fn,
                 devices=jax.devices()[:1])
    finally:
        jax.profiler.stop_trace()
    out["spans"] = _spans(trace_dir)
    return out


def test_plan_span_holds_far_phases(traced_job):
    spans = traced_job["spans"]
    (plan,) = [s for s in spans if s[0] == "repro.plan"]
    assert plan[1] == {"policy": "far", "tasks": 2}
    phases = [s for s in spans if s[0].startswith("repro.plan.")]
    assert sorted(s[0] for s in phases) == [
        "repro.plan.evaluate", "repro.plan.family", "repro.plan.refine"]
    assert all(_parent(spans, s) is plan for s in phases)


def test_served_path_span_tree(traced_job):
    spans = traced_job["spans"]
    (create,) = [s for s in spans if s[0] == "repro.instance.create"]
    (task,) = [s for s in spans if s[0] == "repro.task"]
    (job,) = [s for s in spans if s[0] == "repro.serve"]
    assert create[3] <= task[2]
    assert task[1]["node"] == create[1]["node"] == "T0[0:1]"
    assert _parent(spans, job) is task
    assert job[1]["batch"] == BATCH and job[1]["prompt"] == PROMPT
    assert job[1]["gen"] == GEN
    assert "cached" not in job[1]  # an empty stat: no program was kept
    inner = [s for s in spans if s[0].startswith("repro.serve.")]
    assert all(_parent(spans, s) is job for s in inner)
    names = sorted(s[0] for s in inner)
    assert names == sorted(
        ["repro.serve.build", "repro.serve.init", "repro.serve.prefill"]
        + ["repro.serve.lower"] * 3 + ["repro.serve.compile"] * 3
        + ["repro.serve.decode_step"] * (GEN - 1))
    for stage in ("lower", "compile"):
        assert sorted(s[1]["program"] for s in inner
                      if s[0] == f"repro.serve.{stage}") == [
            "decode", "init", "prefill"]
    steps = sorted(s[1]["step"] for s in inner
                   if s[0] == "repro.serve.decode_step")
    assert steps == list(range(GEN - 1))
    # the stages run one after another, in this order
    order = [s[0].split(".")[-1] for s in sorted(inner, key=lambda s: s[2])]
    assert order[:1] == ["build"] and order[-GEN:] == (
        ["prefill"] + ["decode_step"] * (GEN - 1))
    assert all(a[3] <= b[2] for a, b in zip(
        sorted(inner, key=lambda s: s[2]),
        sorted(inner, key=lambda s: s[2])[1:]))


def test_every_job_span_names_the_mesh_chips(traced_job):
    want = " ".join(map(str, traced_job["mesh_ids"]))
    named = [s for s in traced_job["spans"]
             if s[0].startswith(("repro.instance", "repro.task",
                                 "repro.serve"))]
    assert len(named) == 3 + 1 + 3 * 2 + 2 + GEN - 1
    # a stat that reads as a number comes back as one
    assert {str(s[1]["chips"]) for s in named} == {want}
    assert traced_job["served"]["device_ids"] == traced_job["mesh_ids"]


def test_served_tokens_are_plain_greedy_decoding(traced_job):
    """The ahead-of-time programs serve what jitting the model's own
    prefill and decode step gives, token for token."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke
    from repro.models.model import build_model

    cfg = get_smoke(ARCH)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(0))
    prompts = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, size=(BATCH, PROMPT)).astype(np.int32)
    logits, cache = jax.jit(model.prefill)(params,
                                           {"tokens": jnp.asarray(prompts)})
    decode = jax.jit(model.decode_step)
    want = []
    for _ in range(GEN):
        token = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        want.append(np.asarray(token))
        logits, cache = decode(params, cache, token)
    served = traced_job["served"]["tokens"]
    assert served.shape == (BATCH, GEN)
    np.testing.assert_array_equal(served, np.concatenate(want, axis=1))


def test_chip_ids_survive_the_trace(tmp_path):
    """A stat is cut at its first comma when read back, so chip ids are
    written with spaces."""
    import jax

    from repro.core.spans import chip_ids, span

    class Dev:
        def __init__(self, i):
            self.id = i

    chips = chip_ids([Dev(3), Dev(1), Dev(2)])
    assert chips == "1 2 3"
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with span("repro.test", chips=chips, task=7):
            pass
    finally:
        jax.profiler.stop_trace()
    (got,) = _spans(tmp_path)
    assert got[1] == {"chips": "1 2 3", "task": 7}


def test_scheduler_stays_free_of_jax():
    """Without jax loaded a span is a null context, and planning loads no
    jax."""
    code = (
        "import sys\n"
        "from repro.core.device_spec import V5E_1\n"
        "from repro.core.policy import get_policy\n"
        "from repro.core.problem import Task\n"
        "get_policy('far').plan([Task(0, {1: 1.0})], V5E_1)\n"
        "assert 'jax' not in sys.modules, 'the scheduler imported jax'\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env={"PYTHONPATH": SRC}, timeout=120)
    assert proc.returncode == 0, proc.stderr
