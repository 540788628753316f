"""Share of the engine's step time (dispatch to commit) spent in steps
that carry prefill chunks, over the window."""

from bench.counters import prefill_share


def read(ctx):
    """Percent."""
    return prefill_share(ctx.counters0, ctx.counters1)
