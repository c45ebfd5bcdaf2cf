from collections import Counter

import numpy as np

from bench.lib.traffic import job_backlog, prompt_ids

TRAFFIC = {"shapes": [{"batch": 16, "prompt": 1024},
                      {"batch": 8, "prompt": 2048}],
           "gen": [16, 32], "batches": 3}


def _kinds(batch):
    return Counter((j.batch, j.prompt, j.gen) for j in batch)


def test_every_seed_gives_the_same_work_in_another_order():
    a = job_backlog(TRAFFIC, 8, 1)
    b = job_backlog(TRAFFIC, 8, 2**31 + 12345)
    assert [_kinds(x) for x in a] == [_kinds(x) for x in b]
    assert all(v == 2 for v in _kinds(a[0]).values())
    assert [j.id for x in a for j in x] == list(range(24))
    assert [(j.batch, j.gen) for j in a[0]] != [(j.batch, j.gen)
                                               for j in b[0]] or \
        [j.seed for j in a[0]] != [j.seed for j in b[0]]


def test_same_seed_same_backlog_and_prompts():
    a = job_backlog(TRAFFIC, 4, 7)
    b = job_backlog(TRAFFIC, 4, 7)
    assert a == b
    p = prompt_ids(a[0][0], 151936)
    assert p.shape == (a[0][0].batch, a[0][0].prompt)
    assert np.array_equal(p, prompt_ids(b[0][0], 151936))
    assert p.min() >= 0 and p.max() < 151936
