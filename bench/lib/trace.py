"""Reduction of a profiler trace to the benchmark's device numbers.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote, in one
pass over its planes, and keeps a small neutral form of it:

* ``window_ns``: the host span ``bench.window``;
* ``devices``: each chip's device operations, the ``XLA Ops`` line of its
  ``/device:TPU:<n>`` plane, as ``[name, start_ns, duration_ns]``;
* ``modules``: each chip's program executions, the ``XLA Modules`` line of
  the same plane, one event per run of a jitted program, named by it
  (``jit_prefill_step(<fingerprint>)``): the operations that run inside
  one belong to that program;
* ``spans``: the host spans of the benchmark (``bench.``) and of the
  program (``repro.``), each stat rendered into the name as
  `` key=value`` with a value's spaces written as commas, so
  ``chips="0 1"`` reads ``chips=0,1``.

Everything else reads that form, so it can be checked on a recorded
fixture without a chip.  Host spans and device events share the
profiler's clock.
"""

from __future__ import annotations

import glob
import re
from collections import defaultdict

from bench.lib.window import union_length

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("bench.", "repro.")
WINDOW_SPAN = "bench.window"
CHIPS = re.compile(r"chips=([\d,]+)")


def neutral_name(name: str, stats) -> str:
    """``name`` followed by `` key=value`` for each stat."""
    return name + "".join(
        f" {k}={str(v).replace(' ', ',')}" for k, v in stats)


def _events(line):
    return [[e.name, e.start_ns, e.duration_ns] for e in line.events]


def extract(trace_dir: str) -> dict:
    """Neutral form of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    devices, modules, spans = {}, {}, []
    for plane in ProfileData.from_file(paths[-1]).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            devices[chip], modules[chip] = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[chip] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[chip] = _events(line)
        elif plane.name.startswith("/host:"):
            spans.extend(
                [neutral_name(e.name, e.stats), e.start_ns, e.duration_ns]
                for line in plane.lines for e in line.events
                if e.name.startswith(SPAN_PREFIXES))
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"want one {WINDOW_SPAN} span, found {len(windows)}")
    _, start, dur = windows[0]
    return {"window_ns": [start, start + dur], "devices": devices,
            "modules": modules, "spans": spans}


def _intervals(ops):
    return [(s, s + d) for _, s, d in ops]


def busy_seconds(form: dict) -> dict:
    """Chip -> seconds of the window in which an operation ran on it."""
    lo, hi = form["window_ns"]
    return {chip: union_length(_intervals(ops), lo, hi) * 1e-9
            for chip, ops in form["devices"].items()}


def window_seconds(form: dict) -> float:
    lo, hi = form["window_ns"]
    return (hi - lo) * 1e-9


def _leaves(ops):
    """The operations that hold no other: a loop's operation spans its
    body's, which would otherwise count twice."""
    ordered = sorted(ops, key=lambda e: (e[1], -e[2]))
    parent = [False] * len(ordered)
    open_ = []  # indexes of operations that may still hold the next one
    for i, (_, s, d) in enumerate(ordered):
        while open_ and ordered[open_[-1]][1] + ordered[open_[-1]][2] <= s:
            open_.pop()
        if open_:
            parent[open_[-1]] = True
        open_.append(i)
    return [e for e, p in zip(ordered, parent) if not p]


def top_ops(form: dict, chips, k: int = 10) -> list:
    """The ``k`` operations that took most device time in the window:
    ``[name, seconds per chip]``, averaged over ``chips``; an operation
    that holds others (a loop) counts only through them."""
    lo, hi = form["window_ns"]
    total = defaultdict(float)
    for chip in chips:
        for name, s, d in _leaves(form["devices"].get(chip, ())):
            total[name] += max(0, min(s + d, hi) - max(s, lo))
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9 / len(chips)] for name, ns in ranked]


def _gaps(ops, lo, hi):
    """Idle intervals of one chip inside ``[lo, hi]``."""
    out, end = [], lo
    for s, e in sorted(_intervals(ops)):
        s, e = max(s, lo), min(e, hi)
        if s > end:
            out.append((end, s))
        end = max(end, e)
    if hi > end:
        out.append((end, hi))
    return out


def _label(spans, chip: int, t: int) -> str:
    """What the host was doing for ``chip`` at time ``t``: the innermost
    host span, the benchmark's or the program's, that covers ``t`` and is
    about this chip (a span that names chips) or about the whole host (one
    that names none)."""
    best = None
    for name, s, d in spans:
        if not s <= t < s + d or name == WINDOW_SPAN:
            continue
        m = CHIPS.search(name)
        if m and chip not in {int(c) for c in m.group(1).split(",")}:
            continue
        if best is None or d < best[1]:
            best = (name, d)
    return best[0] if best else "no host span"


def idle_gaps(form: dict, chips, k: int = 10) -> list:
    """The ``k`` longest idle gaps of the window over ``chips``:
    ``[f"chip <n>: <host span>", seconds]``."""
    lo, hi = form["window_ns"]
    gaps = [
        (b - a, chip, (a + b) // 2)
        for chip in chips
        for a, b in _gaps(form["devices"].get(chip, ()), lo, hi)
    ]
    gaps.sort(key=lambda g: -g[0])
    return [[f"chip {chip}: {_label(form['spans'], chip, mid)}", ns * 1e-9]
            for ns, chip, mid in gaps[:k]]
