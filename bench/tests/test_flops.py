import json

import pytest

from bench.lib.flops import job_flops
from conftest import ROOT

TOY = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
       "num_attention_heads": 2, "num_key_value_heads": 1,
       "vocab_size": 10}


def test_prefill_and_each_decode_step_by_hand():
    # per layer: q 8x8, k and v 8x4 each, o 8x8, mlp 3 x 8x16 -> 576
    linear, qk, head = 2 * 576, 8, 8 * 10
    prefill = 2 * linear * 3 + 4 * 2 * qk * (1 + 2 + 3) + 2 * head
    assert job_flops(TOY, 1, 3, 1) == prefill
    step = 2 * linear + 4 * 2 * qk * 4 + 2 * head   # position 3 sees 4 keys
    assert job_flops(TOY, 5, 3, 2) == 5 * (prefill + step)


def test_qwen_prefill_is_about_two_flops_per_parameter_and_token():
    model = json.loads((ROOT / "bench" / "configs" /
                        "v5e1-qwen2.5-3b.json").read_text())["model"]
    matmul_params = 36 * (2048 * 2048 * 2 + 2 * 2048 * 256 + 3 * 2048 * 11008)
    got = job_flops(model, 16, 1024, 1)
    assert got == pytest.approx(2 * matmul_params * 16 * 1024, rel=0.05)
