"""The whole step's share of the chip's peak: the FLOPs that the
configuration's reference counts for a job, and ``job_mfu.live`` over
them."""

import json

import pytest

from bench.lib.traffic import Job
from bench.references.qwen2 import job_flops
from bench.run import _reader
from conftest import ROOT

TOY = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
       "num_attention_heads": 2, "num_key_value_heads": 1,
       "vocab_size": 10}


def _model():
    return json.loads((ROOT / "bench" / "configs" /
                       "v5e1-qwen2.5-3b.json").read_text())["model"]


def test_prefill_and_each_decode_step_by_hand():
    # per layer: q 8x8, k and v 8x4 each, o 8x8, mlp 3 x 8x16 -> 576
    linear, qk, head = 2 * 576, 8, 8 * 10
    prefill = 2 * linear * 3 + 4 * 2 * qk * (1 + 2 + 3) + 2 * head
    assert job_flops(TOY, 1, 3, 1) == prefill
    step = 2 * linear + 4 * 2 * qk * 4 + 2 * head   # position 3 sees 4 keys
    assert job_flops(TOY, 5, 3, 2) == 5 * (prefill + step)


def test_qwen_prefill_is_about_two_flops_per_parameter_and_token():
    matmul_params = 36 * (2048 * 2048 * 2 + 2 * 2048 * 256 + 3 * 2048 * 11008)
    got = job_flops(_model(), 16, 1024, 1)
    assert got == pytest.approx(2 * matmul_params * 16 * 1024, rel=0.05)


# FLOPs of each job kind of the eval-jobs traffic, as the benchmark counted
# them before the count moved to the reference
EVAL_JOBS = {(16, 1024, 16): 94956320456704.0,
             (16, 1024, 32): 96615280934912.0,
             (8, 2048, 16): 96684392579072.0,
             (8, 2048, 32): 97552527523840.0}


@pytest.mark.parametrize("kind", sorted(EVAL_JOBS))
def test_eval_jobs_count_as_before(kind):
    assert job_flops(_model(), *kind) == EVAL_JOBS[kind]


def test_job_mfu_reads_the_configurations_reference():
    """Two FAR batches of the one-chip cell in a 10.948 s window."""
    jobs = [{"job": Job(i, *kind, seed=i)}
            for i, kind in enumerate(sorted(EVAL_JOBS) * 2)]
    run = {"reference": "qwen2", "model": _model(), "jobs": jobs,
           "window_s": 10.948, "chips": [0],
           "peak": {"bf16_flops_per_s": 197e12}}
    want = 100.0 * 2 * sum(EVAL_JOBS.values()) / (10.948 * 197e12)
    assert _reader("job_mfu.live")(run) == want
    assert want == pytest.approx(35.78, abs=0.01)
