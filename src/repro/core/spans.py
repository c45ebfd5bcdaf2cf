"""Named host spans on the profiler's clock.

``span(name, **stats)`` is ``jax.profiler.TraceAnnotation(name, **stats)``:
with a profiler running, the span lands in its trace beside the device's
operations; without one it costs well under a microsecond.  Every name
starts with ``repro.``.  A stat value is cut at its first comma when read
back, so chip ids are written space-separated (:func:`chip_ids`).

This module never imports jax: where jax is not loaded yet (the
scheduler on its own), nothing can be traced, and a span is a null
context.
"""

from __future__ import annotations

import contextlib
import sys


def span(name: str, **stats):
    """Context manager that records ``name`` with ``stats`` while a
    profiler runs."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name, **stats)


def chip_ids(devices) -> str:
    """``"0 1"``: the sorted ids of ``devices``, as a span's ``chips``."""
    return " ".join(str(i) for i in sorted(d.id for d in devices))
