"""End-to-end metric arithmetic over one run's raw samples.

Each request record carries its due time, the times of its tokens and
whether it failed.  Tails are exact percentiles of the raw samples
(linear interpolation between order statistics), never bucketed.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class RequestRecord:
    rid: int
    due: float                   # host clock, when it was due
    submitted: float             # host clock, when the harness submitted
    prompt_len: int
    max_new: int
    tok_t: List[float] = dataclasses.field(default_factory=list)
    failed: bool = False
    finished: bool = False
    bind: Optional[float] = None  # first admission (flight recorder)


def percentile(values, q: float) -> Optional[float]:
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, float), q))


def ttft_samples(recs, w0: float, w1: float) -> List[float]:
    """Seconds from due time to first token for every request due in
    [w0, w1).  One with no first token by w1, or that failed, enters at
    w1 - due: its time to first token is at least that."""
    out = []
    for r in recs:
        if not (w0 <= r.due < w1):
            continue
        if r.tok_t and r.tok_t[0] <= w1 and not r.failed:
            out.append(r.tok_t[0] - r.due)
        else:
            out.append(w1 - r.due)
    return out


def itl_samples(recs, w0: float, w1: float) -> List[float]:
    """Every gap between consecutive tokens of one request whose later
    token falls in [w0, w1]."""
    out = []
    for r in recs:
        t = r.tok_t
        out.extend(b - a for a, b in zip(t, t[1:]) if w0 <= b <= w1)
    return out


def tokens_in(recs, w0: float, w1: float) -> int:
    return sum(1 for r in recs for t in r.tok_t if w0 <= t <= w1)


def queue_wait_samples(recs, w0: float, w1: float) -> List[float]:
    """Due time to first admission for requests due in [w0, w1); one
    not admitted by w1 enters at w1 - due."""
    out = []
    for r in recs:
        if w0 <= r.due < w1:
            out.append((r.bind if r.bind is not None and r.bind <= w1
                        else w1) - r.due)
    return out


def lateness(recs) -> List[float]:
    """How late the harness submitted each request after it was due."""
    return [r.submitted - r.due for r in recs]
