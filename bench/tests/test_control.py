"""The control of the logit-gap check, at a size a test run can hold.

Qwen2.5-3B's published widths with 12 of its 36 layers, a vocabulary of
8192 and a job of two 512-token prompts with 16 tokens served, run through
the live cell's window and judged by its own check: the program's served
tokens come out correct, and the float8 control, put in the program's
place, comes out not correct.  (At the cell's own size the readings are
in ``PERF.md``.)
"""

import dataclasses
import json

import pytest

from bench.run import judge
from conftest import ROOT

LAYERS, VOCAB, PROMPT, GEN = 12, 8192, 512, 16
TRAFFIC = {"shapes": [{"batch": 2, "prompt": PROMPT}], "gen": [GEN],
           "batches": 2, "sample_rows": 2}


@pytest.fixture(scope="module")
def mid_size():
    import repro.configs as configs
    import repro.launch.serve as serve

    cfg = dataclasses.replace(configs.get("qwen2.5-3b"), n_layers=LAYERS,
                              vocab_size=VOCAB)
    config = json.loads(
        (ROOT / "bench" / "configs" / "v5e1-qwen2.5-3b.json").read_text())
    config["model"].update(num_hidden_layers=LAYERS, vocab_size=VOCAB)
    config["weights"]["padded_vocab"] = cfg.padded_vocab()
    config["pool"]["batch_jobs"] = 1
    mp = pytest.MonkeyPatch()
    mp.setattr(configs, "get", lambda arch: cfg)
    mp.setattr(serve, "get", lambda arch: cfg)
    yield config
    mp.undo()


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_control_fails_the_limit(mid_size, seed):
    import jax

    from bench.runners.live import LiveCell

    live = LiveCell(mid_size, TRAFFIC, jax.devices()[:1], seed)
    win = live.window(1e-3)         # the first batch, one job, and no more
    assert len(win["ran"]) == 1
    served, control = live.check(win, control=True)
    assert judge(len(win["ran"]), served), served
    assert not judge(len(win["ran"]), control), control
