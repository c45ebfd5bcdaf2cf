"""One run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything comes from ``BENCHMARK.json`` by name: the cell names its
configuration (``bench/configs/<config>.json``, which names its runner in
``bench/runners/`` and its plain reference in ``bench/references/``) and
its traffic (``bench/traffic/<mix>.json``); each metric is read by
``bench/metrics/<metric>.py``.  The run refuses any platform but a TPU.
It sets up, measures for ``--seconds``, checks what the timed path
produced against the reference, and prints one JSON object as the last
line of stdout.  With ``--trace 1`` the window runs under the profiler
and the per-layer metrics are reported instead of the end-to-end ones.
The numbers compared for ``correct`` end stderr, each beside its limit.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import device, trace  # noqa: E402


def _entry(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"bench: BENCHMARK.json has no {what} {name!r}")


def _reader(name: str):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def judge(attempted: int, checks: dict) -> bool:
    """A run is correct when it attempted work and no compared number
    exceeds its limit."""
    return attempted > 0 and all(
        value <= limit for value, limit in checks.values())


class Tracer(contextlib.AbstractContextManager):
    """Profiles the window into a temporary directory, keeps the neutral
    form of the trace, and removes the directory.  The Python tracer stays
    off: it records every Python call, and the jobs trace their models in
    Python, so it would stretch the window it measures."""

    form = None

    def __enter__(self):
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()
        try:
            if exc[0] is None:
                self.form = trace.extract(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _use_compile_cache(jax) -> None:
    """The persistent cache at a fixed path in the checkout, whatever the
    environment says, unbounded, and for every program however fast it
    compiled: only a checkout's first run compiles, and two checkouts
    share nothing."""
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

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
    runner = importlib.import_module(f"bench.runners.{config['runner']}")
    tracer = Tracer() if args.trace else contextlib.nullcontext()
    rec = runner.run(config, traffic, devices, args.seed, args.seconds,
                     tracer)
    rec.update(setup_s=rec["setup_end"] - T0, window_s=rec["hi"] - rec["lo"],
               model=config["model"], reference=config["reference"],
               peak=device.peaks(devices[0].device_kind),
               trace=getattr(tracer, "form", None))

    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in bench[kind]:
        if cell["name"] not in m.get("workloads", [cell["name"]]):
            continue
        value = _reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = device.describe(devices)
    dev["memory_peak_bytes"] = rec["memory_peak_bytes"]
    out = {"correct": None, "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": metrics, "device": dev}
    if args.trace:
        form, chips = rec["trace"], rec["chips"]
        busy = trace.busy_seconds(form)
        dev["busy_s"] = sum(busy.get(c, 0.0) for c in chips) / len(chips)
        dev["window_s"] = trace.window_seconds(form)
        out["breakdown"] = {"device_ops": trace.top_ops(form, chips),
                            "idle_gaps": trace.idle_gaps(form, chips)}
    checks = rec["checks"]
    out["correct"] = judge(rec["attempted"], checks)
    for err in rec["errors"]:
        print(f"bench: {err}", file=sys.stderr)
    for name, (value, limit) in checks.items():
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    out["checks"] = {name: {"value": value, "limit": limit}
                     for name, (value, limit) in checks.items()}
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
