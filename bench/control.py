"""Readings that set the limit of a live cell's logit-gap check.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 3 --seconds 1

For each seed, in one process: the cell's window as the benchmark runs
it (here without warm-up, so the first seed compiles), then the cell's
own check (``LiveCell.check``) on its sample: the program's readings and
its verdict.  For the first ``--control-seeds`` seeds the same check is
also made with the float8 control in the program's place, the gap of the
token it puts first at each served position, and judged by the same rule
(``bench.run.judge``).  One JSON line per seed on stdout: each side's
verdict, its mean gap (the number compared) and, beside it, the widest
gap, the median and the share of positions off the reference's first
choice.  The benchmark's own runs never run the control.
"""

import argparse
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import device  # noqa: E402
from bench.run import _entry, _use_compile_cache, judge  # noqa: E402


def _stats(gaps) -> dict:
    return {"mean": float(gaps.mean()), "widest": float(gaps.max()),
            "median": float(np.median(gaps)),
            "off_first": float((gaps > 0).mean()), "positions": len(gaps)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args()

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = _entry(bench["workloads"], args.workload, "workload")
    config = json.loads(
        (ROOT / _entry(bench["configs"], cell["config"], "config")["file"])
        .read_text())
    traffic = json.loads(
        (ROOT / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    devices = device.require_tpu(cell["chips"])

    import jax

    _use_compile_cache(jax)
    from bench.runners.live import LiveCell

    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        live = LiveCell(config, traffic, devices, seed)
        win = live.window(args.seconds)
        control = k < args.control_seeds
        verdicts = live.check(win, control=control)
        sides = zip(("served", "control"), verdicts if control else
                    (verdicts,))
        attempted = len(win["ran"])
        print(json.dumps({
            "seed": seed,
            **{side: {"correct": judge(attempted, checks),
                      **({} if live.gaps[side] is None
                         else _stats(live.gaps[side])),
                      "checks": {n: v for n, (v, _) in checks.items()}}
               for side, checks in sides},
            "seconds": time.perf_counter() - t0,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
