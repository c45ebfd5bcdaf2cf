"""A run of the live cell on the CPU at a small size, with the chip check
skipped, and with the timed path broken underneath: each fault has to
turn ``correct`` false.  The window closes after its first batch.  Each
test runs on both deployments: one chip, and the four-chip host on four
CPU devices, planned by FAR over ``V5E_2X2`` and run on its sub-meshes."""

import contextlib

import numpy as np
import pytest

from bench.run import judge
from bench.runners import live

pytestmark = pytest.mark.parametrize(
    "small_live", ["v5e1-qwen2.5-3b", "v5e2x2-qwen2.5-3b"], indirect=True)


def _run(config, traffic, seed=11):
    import jax

    from repro.core.device_spec import SPECS

    chips = SPECS[config["pool"]["spec"]].n_slices
    return live.run(config, traffic, jax.devices()[:chips], seed, 1e-3,
                    contextlib.nullcontext())


def _wrong(rec) -> list:
    """The checks a run failed; any failed check makes it not correct."""
    wrong = [name for name, (value, limit) in rec["checks"].items()
             if not value <= limit]
    assert judge(rec["attempted"], rec["checks"]) == (not wrong)
    return wrong


def _patch_serve(monkeypatch, change):
    """Break the window's serve() calls (set-up's warm-up stays sound)."""
    warm_up = live.LiveCell.warm_up

    def then_break(self):
        warm_up(self)
        real = self.serve
        self.serve = lambda *args: change(real(*args))

    monkeypatch.setattr(live.LiveCell, "warm_up", then_break)


def test_sound_run_passes_its_structural_checks(small_live):
    config, traffic = small_live
    rec = _run(config, traffic)
    assert rec["attempted"] == config["pool"]["batch_jobs"]
    assert rec["failed"] == 0
    assert [n for n in _wrong(rec) if n != "mean_logit_gap"] == []
    assert np.isfinite(rec["checks"]["mean_logit_gap"][0])
    assert rec["hi"] > rec["lo"] >= rec["setup_end"]
    # every chip of the deployment ran a job
    assert {c for j in rec["jobs"] for c in j["chips"]} == set(rec["chips"])


def test_an_answer_altered_where_it_is_produced_fails(small_live,
                                                      monkeypatch):
    config, traffic = small_live

    def alter(out):
        tokens = (out["tokens"] + 1) % config["model"]["vocab_size"]
        return dict(out, tokens=tokens)

    _patch_serve(monkeypatch, alter)
    assert _wrong(_run(config, traffic)) == ["mean_logit_gap"]


def test_an_answer_off_its_instance_fails(small_live, monkeypatch):
    config, traffic = small_live
    _patch_serve(monkeypatch, lambda out: dict(out, device_ids=[99]))
    assert "misplaced_jobs" in _wrong(_run(config, traffic))


def test_jobs_out_of_planned_order_fail(small_live, monkeypatch):
    import repro.runtime.live as program_live

    config, traffic = small_live
    real = program_live.run_live

    def reversed_order(assignment, spec, task_fn, devices=None):
        for key in assignment.node_tasks:
            assignment.node_tasks[key] = assignment.node_tasks[key][::-1]
        try:
            return real(assignment, spec, task_fn, devices=devices)
        finally:
            for key in assignment.node_tasks:
                assignment.node_tasks[key] = assignment.node_tasks[key][::-1]

    monkeypatch.setattr(program_live, "run_live", reversed_order)
    assert _wrong(_run(config, traffic)) == ["misordered_jobs"]


def test_a_job_that_fails_fails_the_run(small_live, monkeypatch):
    config, traffic = small_live

    def crash(out):
        raise RuntimeError("job lost")

    _patch_serve(monkeypatch, crash)
    rec = _run(config, traffic)
    assert rec["failed"] > 0
    assert {"bad_answers", "failed_batches"} <= set(_wrong(rec))
