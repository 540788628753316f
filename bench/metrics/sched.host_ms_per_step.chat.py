"""Host time on the engine's thread per step: the seconds of the
``serve.step_begin`` and ``serve.step_commit`` spans in the traced
window, less the blocking reads of step results (``serve.readback``)
inside them, over the number of ``serve.step_commit`` spans. Idle
iterations, which dispatch nothing, add their ``step_begin`` time and
no step. None without the program's spans in the trace."""

from bench.spans import READBACK, STEP_BEGIN, STEP_COMMIT


def read(ctx):
    """Milliseconds."""
    spans = (getattr(ctx, "spans", None) or {}).get("spans", {})
    steps = spans.get(STEP_COMMIT, {}).get("count", 0)
    if not steps:
        return None
    secs = sum(spans.get(n, {}).get("total_s", 0.0)
               for n in (STEP_BEGIN, STEP_COMMIT))
    secs -= spans.get(READBACK, {}).get("total_s", 0.0)
    return 1e3 * secs / steps
