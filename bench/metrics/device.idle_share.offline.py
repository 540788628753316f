"""Share of the traced window in which no operation ran on the device."""

from bench.trace import idle_share


def read(ctx):
    """Percent."""
    return idle_share(ctx.trace)
