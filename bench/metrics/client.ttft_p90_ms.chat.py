"""90th percentile of time to first token over every request due in the
chat window, from when it was due, as its client saw it. The load goes on
past the close until each of them has its first token (``ctx.t_end``);
one with none by then counts at its wait so far. A window holds some 30
requests, so this tail has about 3 beyond it and swings with the order a
seed deals: too widely for an end-to-end bound, so it is read per layer.
It moves with ``tpot_p90_ms`` where the step slows, since a prompt's
chunks and the decode tokens ride the same steps."""

from bench.stats import percentile, ttft_values


def read(ctx):
    """Milliseconds."""
    vals = ttft_values(ctx.records, ctx.t0, ctx.t1, ctx.t_end)
    return percentile(vals, 90) * 1e3 if vals else None
