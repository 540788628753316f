"""Arithmetic on the engine's own counters, as deltas across the window.

``ServeEngine.phase_time`` holds host-clock seconds from dispatch to
commit of each step kind (``decode``: pure decode blocks; ``mixed`` and
``prefill``: steps that carry prompt chunks, with and without decode
rows). ``decode_steps`` counts decode positions of both kinds of step;
``mixed_steps`` counts the fused steps, each of which ran one.
"""

from __future__ import annotations


def delta(c0: dict, c1: dict, key: str):
    """The change of counter ``key`` across the window."""
    return c1[key] - c0[key]


def phase_delta(c0: dict, c1: dict) -> dict:
    """The change of each phase clock across the window."""
    return {k: c1["phase_time"][k] - c0["phase_time"][k]
            for k in c1["phase_time"]}


def decode_step_seconds(c0: dict, c1: dict):
    """Seconds per decode position of the pure-decode steps, or None."""
    positions = (delta(c0, c1, "decode_steps")
                 - delta(c0, c1, "mixed_steps"))
    if positions <= 0:
        return None
    return phase_delta(c0, c1)["decode"] / positions


def prefill_share(c0: dict, c1: dict):
    """Percent of step time in steps that carry prompt chunks, or None."""
    ph = phase_delta(c0, c1)
    total = sum(ph.values())
    if total <= 0:
        return None
    return 100.0 * (ph["mixed"] + ph["prefill"]) / total
