"""90th percentile of the prefill wait, from the engine's ``admit_time``
to its ``first_token_at`` stamp (the prompt's chunks riding the fused
steps), over the requests due in the chat window and admitted by the end
of the follow-on. One with no first token by then counts at its wait so
far. None where the program stamps no admission."""

from bench.spans import stamp_waits
from bench.stats import percentile


def read(ctx):
    """Milliseconds."""
    vals = stamp_waits(ctx, "admit_time", "first_token_at")
    return percentile(vals, 90) * 1e3 if vals else None
