"""90th percentile of the queue wait, from the engine's ``submit_time``
to its ``admit_time`` stamp (a slot bound, the prompt's chunks planned),
over every request due in the chat window. One not admitted by the end
of the follow-on counts at its wait so far, as
``client.ttft_p90_ms.chat`` counts it. None where the program stamps no
admission."""

from bench.spans import stamp_waits
from bench.stats import percentile


def read(ctx):
    """Milliseconds."""
    vals = stamp_waits(ctx, "submit_time", "admit_time")
    return percentile(vals, 90) * 1e3 if vals else None
