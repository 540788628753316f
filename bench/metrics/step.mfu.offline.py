"""Model FLOPs of the work completed in the window, over the window times
the chips times their bf16 peak: every prompt whose first token came in
the window, and every later output token delivered in it, each with its
attention at its own context length (``bench/flops.py``)."""

from bench import flops


def read(ctx):
    """Percent of peak."""
    total = 0.0
    for r in ctx.records:
        ts = r.token_times
        if ts and ctx.t0 <= ts[0] <= ctx.t1:
            total += flops.prompt_flops(ctx.spec, r.prompt_len)
        total += sum(flops.decode_flops(ctx.spec, r.prompt_len, j)
                     for j in range(1, len(ts)) if ctx.t0 <= ts[j] <= ctx.t1)
    peak = ctx.peaks["bf16_flops_per_s"] * ctx.chips * (ctx.t1 - ctx.t0)
    return 100.0 * total / peak
