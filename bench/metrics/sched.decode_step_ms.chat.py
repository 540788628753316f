"""Time per decode position of the engine's pure-decode steps: the
``phase_time["decode"]`` delta over the window, over the decode positions
those steps ran (a block of k positions counts k; the one decode position
of each fused prefill step is not among them)."""

from bench.counters import decode_step_seconds


def read(ctx):
    """Milliseconds."""
    s = decode_step_seconds(ctx.counters0, ctx.counters1)
    return None if s is None else s * 1e3
