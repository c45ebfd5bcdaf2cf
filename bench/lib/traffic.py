"""The general generator of job backlogs, driven by a traffic file.

A traffic file (``bench/traffic/<mix>.json``) names the job shapes and
the served lengths; the deployment names how many jobs go to the
scheduler at once.  Every batch holds each (shape, length) combination
equally often, so every seed gives the same work in another order: the
seed picks the order and each job's prompt seed.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Job:
    id: int
    batch: int      # sequences served together
    prompt: int     # prompt tokens per sequence
    gen: int        # tokens served per sequence, greedily
    seed: int       # the prompt ids are drawn from this seed

    @property
    def tokens(self) -> int:
        """Prompt and served tokens of all its sequences."""
        return self.batch * (self.prompt + self.gen)


def job_backlog(traffic: dict, jobs_per_batch: int, seed: int
                ) -> list[list[Job]]:
    """``traffic["batches"]`` batches of ``jobs_per_batch`` jobs each."""
    kinds = [(s["batch"], s["prompt"], g)
             for s in traffic["shapes"] for g in traffic["gen"]]
    if jobs_per_batch % len(kinds):
        raise ValueError(f"{jobs_per_batch} jobs per batch do not hold the "
                         f"{len(kinds)} job kinds equally often")
    mix = kinds * (jobs_per_batch // len(kinds))
    rng = np.random.default_rng(seed)
    batches = []
    for b in range(traffic["batches"]):
        order = rng.permutation(len(mix))
        seeds = rng.integers(0, 2**31 - 1, size=len(mix))
        batches.append([
            Job(b * len(mix) + i, *mix[k], int(s))
            for i, (k, s) in enumerate(zip(order, seeds))
        ])
    return batches


def prompt_ids(job: Job, vocab: int) -> np.ndarray:
    """The ``(batch, prompt)`` token ids a job's prompt seed stands for."""
    rng = np.random.default_rng(job.seed)
    return rng.integers(0, vocab, size=(job.batch, job.prompt)).astype(
        np.int32)
