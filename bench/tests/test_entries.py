"""The entries of ``BENCHMARK.json`` hold together: each cell finds its
files, few cells take four chips, and each per-layer metric's
``workloads`` names exactly the cells that report it.  A configuration
that joins the benchmark edits these lists."""

import importlib
import json

import pytest

from bench.lib import device
from bench.run import _reader
from conftest import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = {w["name"]: w for w in BENCH["workloads"]}
CONFIGS = {c["name"]: c for c in BENCH["configs"]}


def _config(cell: str) -> dict:
    return json.loads((ROOT / CONFIGS[CELLS[cell]["config"]]["file"])
                      .read_text())


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_each_cell_finds_its_configuration_traffic_runner_and_reference(
        cell):
    config = _config(cell)
    assert config["name"] == CELLS[cell]["config"]
    bench = ROOT / "bench"
    assert (bench / "traffic" / f"{CELLS[cell]['traffic']}.json").is_file()
    assert (bench / "runners" / f"{config['runner']}.py").is_file()
    assert (bench / "references" / f"{config['reference']}.py").is_file()


def test_at_most_half_the_cells_take_four_chips():
    chips = [w["chips"] for w in CELLS.values()]
    assert set(chips) <= {1, 4}
    assert chips.count(4) <= max(1, len(chips) // 2)


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_metric_workloads_name_only_cells(kind):
    for m in BENCH[kind]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS), m["name"]


def test_each_per_layer_metric_moves_an_end_to_end_metric_of_its_cells():
    reports = {m["name"]: set(m.get("workloads", CELLS))
               for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert set(m.get("workloads", CELLS)) <= reports[m["moves"]], \
            m["name"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_each_cell_lists_setup_and_another_end_to_end_metric(cell):
    names = {m["name"] for m in BENCH["end_to_end"]
             if cell in m.get("workloads", CELLS)}
    assert "setup_s" in names and len(names) >= 2


@pytest.mark.parametrize(
    "cell,small_live",
    [(cell, CELLS[cell]["config"]) for cell in sorted(CELLS)],
    indirect=["small_live"])
def test_each_cell_is_listed_by_exactly_the_per_layer_metrics_it_reports(
        cell, small_live):
    """The cell on the CPU at a small size under the benchmark's tracer,
    its record completed as ``bench/run.py`` completes it (with a v5e
    chip's peaks: the CPU has none), then every per-layer reader: those
    that read something list the cell, and those that list it read
    something."""
    import jax

    from bench.run import Tracer

    config, traffic = small_live
    runner = importlib.import_module(f"bench.runners.{config['runner']}")
    tracer = Tracer()
    rec = runner.run(config, traffic, jax.devices()[:CELLS[cell]["chips"]],
                     11, 1e-3, tracer)
    rec.update(setup_s=rec["setup_end"], window_s=rec["hi"] - rec["lo"],
               model=config["model"], reference=config["reference"],
               peak=device.peaks("TPU v5 lite"), trace=tracer.form)
    reads = {m["name"] for m in BENCH["per_layer"]
             if _reader(m["name"])(rec) is not None}
    listed = {m["name"] for m in BENCH["per_layer"]
              if cell in m.get("workloads", CELLS)}
    assert listed
    assert reads == listed
