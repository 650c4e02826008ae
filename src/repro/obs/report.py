"""Roll-ups of a recorded span stream (:mod:`repro.obs.tracer`)."""

from __future__ import annotations


def category_rollup(events) -> dict:
    """Total wall microseconds per span category ("X" events only)."""
    out: dict = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        cat = ev.get("cat", "?")
        out[cat] = out.get(cat, 0.0) + float(ev.get("dur", 0.0))
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))
