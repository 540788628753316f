"""End-to-end metrics from client-side records, on the host clock.

Every time here is on one clock (the client's event loop). A request is
timed from when it was due, not from when it was sent, so a stall that
delays later submissions counts against them.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional


@dataclasses.dataclass
class Record:
    """What one client saw of one request."""

    uid: int
    due: float                       # absolute time the request was due
    prompt_len: int
    max_new: int
    greedy: bool
    sent: Optional[float] = None     # when the client handed it over
    token_times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    status: Optional[str] = None     # the engine's terminal state

    @property
    def failed(self) -> bool:
        """Shed at admission or ended in an error."""
        return self.status in ("shed", "errored")


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100), linear between closest ranks
    (numpy's default method)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def due_in(records, t0: float, t1: float) -> list:
    """Records of the requests due in the window ``[t0, t1)``."""
    return [r for r in records if t0 <= r.due < t1]


def ttft_values(records, t0: float, t1: float, t_end: float) -> list:
    """Seconds from due to first token, for every request due in the
    window ``[t0, t1)``; its first token is waited for until ``t_end``
    (the window's close, or later while the load goes on), and a request
    with none by then (shed, failed or still waiting) counts at its wait
    so far, ``t_end - due``."""
    out = []
    for r in due_in(records, t0, t1):
        first = r.token_times[0] if r.token_times else None
        end = first if first is not None and first <= t_end else t_end
        out.append(end - r.due)
    return out


def tpot_values(records, t0: float, t1: float) -> list:
    """Mean seconds between tokens, ``(last - first) / (n - 1)`` over the
    tokens delivered by ``t1``, for requests due in the window that got at
    least two."""
    out = []
    for r in due_in(records, t0, t1):
        ts = [t for t in r.token_times if t <= t1]
        if len(ts) >= 2:
            out.append((ts[-1] - ts[0]) / (len(ts) - 1))
    return out


def tokens_in(records, t0: float, t1: float) -> int:
    """Output tokens delivered to clients inside ``[t0, t1]``."""
    return sum(1 for r in records for t in r.token_times if t0 <= t <= t1)
