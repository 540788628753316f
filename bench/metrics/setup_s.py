"""Process start to window open: weights, deployment, engine, compiles or
cache loads, warm-up."""


def read(ctx):
    """Seconds."""
    return ctx.setup_s
