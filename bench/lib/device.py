"""The chip the run is on: its presence, its peaks and its memory."""

from __future__ import annotations

import json
import pathlib
import sys

PEAKS = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"


def require_tpu(chips: int):
    """The first ``chips`` TPU devices.  Any other platform, or fewer
    chips, ends the process with code 2 and no result."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: the cell needs {chips} TPU chip(s); jax found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(2)
    return devs[:chips]


def peaks(kind: str) -> dict:
    """Published peaks of one chip of ``kind``; an unknown kind is an
    error, not a default."""
    table = json.loads(PEAKS.read_text())["chips"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS.name}; "
                       f"have {sorted(table)}")
    return table[kind]


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
