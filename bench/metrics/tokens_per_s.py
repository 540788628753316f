"""Output tokens delivered to clients inside the window, over its length."""

from bench.stats import tokens_in


def read(ctx):
    """Tokens per second."""
    return tokens_in(ctx.records, ctx.t0, ctx.t1) / (ctx.t1 - ctx.t0)
