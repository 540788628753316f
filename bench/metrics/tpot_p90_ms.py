"""90th percentile of the mean gap between a request's tokens, over
requests due in the window that received two or more by its close."""

from bench.stats import percentile, tpot_values


def read(ctx):
    """Milliseconds."""
    vals = tpot_values(ctx.records, ctx.t0, ctx.t1)
    return percentile(vals, 90) * 1e3 if vals else None
