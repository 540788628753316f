"""Share of the traced window in which no operation ran on the device
and the engine's thread was between steps: no ``serve.step_begin`` or
``serve.step_commit`` span open (every other ``serve.*`` span lies
inside one of them), so the idle time went to the frontend's hand-off
from one step to the next. Part of ``device.idle_share.chat``. None
without a device plane in the trace."""

from bench.spans import SERVE


def read(ctx):
    """Percent."""
    spans = getattr(ctx, "spans", None)
    if not spans or not spans["devices"] or spans["window_s"] <= 0:
        return None
    between = sum(v for k, v in spans["idle_s"].items()
                  if not k.startswith(SERVE))
    return 100.0 * between / spans["window_s"]
