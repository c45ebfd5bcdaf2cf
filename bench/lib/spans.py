"""The per-job split of the served path that the program's spans give.

The program records ``repro.*`` host spans (``repro.core.spans``) with
stats, on the profiler's clock; ``bench.lib.trace.extract`` keeps them in
its neutral form, each stat in the name (``repro.serve chips=0,1
batch=16 ...``).  The helpers here read that form.
"""

from __future__ import annotations

import statistics

from bench.lib.trace import CHIPS

SERVE = "repro.serve"
STAGES = ("build", "lower", "compile", "init", "prefill", "decode_step")


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
