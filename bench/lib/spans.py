"""The program's own spans in a profiler trace, and the per-job split of
the served path that they give.

The program records ``repro.*`` host spans (``repro.core.spans``) with
stats, on the profiler's clock.  ``read`` keeps them in the neutral form
of ``bench.lib.trace``: ``[name, start_ns, duration_ns]``, each stat
rendered into the name as `` key=value``, a value's spaces written as
commas, so ``chips="0 1"`` reads ``chips=0,1`` and ``trace._label``
attributes an idle gap to the innermost program span on that chip.

``bench.lib.trace.extract`` does not keep these spans yet; a form that
holds them is ``trace.extract(dir)`` with ``read(dir)`` added to its
``spans``.
"""

from __future__ import annotations

import glob
import statistics

from bench.lib.trace import CHIPS, _gaps
from bench.lib.window import union_length

PREFIX = "repro."
SERVE = "repro.serve"
STAGES = ("build", "lower", "compile", "init", "prefill", "decode_step")


def neutral_name(name: str, stats) -> str:
    """``name`` followed by `` key=value`` for each stat."""
    return name + "".join(
        f" {k}={str(v).replace(' ', ',')}" for k, v in stats)


def read(trace_dir: str) -> list:
    """Every ``repro.`` host span of the newest trace under ``trace_dir``,
    in the neutral form."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return [
        [neutral_name(e.name, e.stats), e.start_ns, e.duration_ns]
        for plane in ProfileData.from_file(paths[-1]).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name.startswith(PREFIX)
    ]


def base(name: str) -> str:
    return name.split(" ", 1)[0]


def chips_of(name: str):
    m = CHIPS.search(name)
    return tuple(int(c) for c in m.group(1).split(",")) if m else None


def _inside(form, prefix: str) -> list:
    lo, hi = form["window_ns"]
    return [s for s in form["spans"]
            if base(s[0]) == prefix and lo <= s[1] and s[1] + s[2] <= hi]


def jobs(form: dict) -> list:
    """The window's ``repro.serve`` spans, each with the ``repro.serve.*``
    spans it holds on its chips: ``{"chips", "seconds", "stages"}``, where
    ``stages`` maps a stage (``STAGES``) to the seconds of each of its
    spans, in order."""
    out = []
    for name, start, dur in sorted(_inside(form, SERVE), key=lambda s: s[1]):
        chips = chips_of(name)
        stages = {stage: [] for stage in STAGES}
        for n, s, d in sorted(form["spans"], key=lambda s: s[1]):
            stage = base(n)[len(SERVE) + 1:]
            if (base(n).startswith(SERVE + ".") and stage in stages
                    and chips_of(n) == chips
                    and start <= s and s + d <= start + dur):
                stages[stage].append(d * 1e-9)
        out.append({"chips": chips, "seconds": dur * 1e-9, "stages": stages})
    return out


def per_job(form: dict, *stages: str):
    """Seconds per job in ``stages``, summed over the window's jobs; None
    where the window holds no job."""
    js = jobs(form)
    if not js:
        return None
    return sum(sum(j["stages"][s]) for j in js for s in stages) / len(js)


def mean_ms(form: dict, name: str):
    """Mean duration of the window's spans called ``name``, in ms."""
    spans = _inside(form, name)
    return 1e-6 * statistics.fmean(d for _, _, d in spans) if spans else None


def decode_step_ms(form: dict):
    steps = [d for j in jobs(form) for d in j["stages"]["decode_step"]]
    return 1e3 * statistics.median(steps) if steps else None


def idle_share_under(form: dict, chips, names) -> float | None:
    """Share (0..1) of the chips' idle seconds in the window that lie under
    a span whose name is in ``names`` and that is about the same chip."""
    lo, hi = form["window_ns"]
    idle = under = 0
    for chip in chips:
        cover = [(s, s + d) for n, s, d in form["spans"]
                 if base(n) in names and chip in (chips_of(n) or ())]
        for a, b in _gaps(form["devices"].get(chip, ()), lo, hi):
            idle += b - a
            under += _covered(cover, a, b)
    return under / idle if idle else None


def busy_share_under(form: dict, chips, name: str) -> float | None:
    """Share (0..1) of the chips' device time in the window that lies
    inside a span called ``name`` about the same chip."""
    lo, hi = form["window_ns"]
    busy = under = 0
    for chip in chips:
        cover = [(s, s + d) for n, s, d in form["spans"]
                 if base(n) == name and chip in (chips_of(n) or ())]
        ops = [(s, s + d) for _, s, d in form["devices"].get(chip, ())]
        busy += _covered(ops, lo, hi)
        under += sum(_covered(ops, max(a, lo), min(b, hi))
                     for a, b in _merged(cover) if a < hi and b > lo)
    return under / busy if busy else None


def _covered(intervals, lo, hi):
    """Length of ``[lo, hi]`` that ``intervals`` cover.  (``union_length``
    counts an interval that lies wholly after ``hi`` as negative.)"""
    return union_length([(a, b) for a, b in intervals if a < hi and b > lo],
                        lo, hi)


def _merged(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out
