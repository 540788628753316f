"""Share of the reserved KV cache that holds tokens, weighted by step:
the engine's ``kv_used_tokens`` (cache positions holding a live
request's tokens, left padding excluded) over its ``kv_capacity_tokens``
(the positions the layout holds), both summed over the steps dispatched
in the window. None where the program keeps no such counters."""

from bench.counters import delta


def read(ctx):
    """Percent."""
    c0, c1 = ctx.counters0, ctx.counters1
    if c0.get("kv_capacity_tokens") is None:
        return None
    cap = delta(c0, c1, "kv_capacity_tokens")
    return 100.0 * delta(c0, c1, "kv_used_tokens") / cap if cap else None
