import json
import os
import subprocess
import sys

from conftest import ROOT


def test_a_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         json.loads((ROOT / "BENCHMARK.json").read_text())
         ["workloads"][0]["name"],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert "metrics" not in done.stdout
    assert "TPU" in done.stderr
