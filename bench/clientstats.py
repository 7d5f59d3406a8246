"""What the client saw: one record per request, and the window's numbers.

Times are host-clock seconds (``time.perf_counter``). A token's time is
when ``poll`` first showed it, after the ``step()`` that produced it.
Every statistic here covers the whole measured window ``[t0, t1]``: a
request still waiting at the close counts at its age then, and a rate
divides by the whole window.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence

import numpy as np

__all__ = ["Record", "percentile", "ttfts", "inter_token_gaps",
           "tokens_in_window"]


@dataclasses.dataclass
class Record:
    due: float                  # when the request was due to be sent
    prompt_len: int
    max_new: int
    tok_times: List[float] = dataclasses.field(default_factory=list)
    status: Optional[str] = None   # terminal status once done
    rid: int = -1
    prompt: Optional[np.ndarray] = None
    tokens: tuple = ()             # served tokens, once done


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank ``q``-th percentile (the smallest value with at least
    ``q`` percent of the values at or below it); None for no values."""
    if not values:
        return None
    v = sorted(values)
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])


def ttfts(records: Sequence[Record], t0: float, t1: float) -> List[float]:
    """Seconds from due time to first token, for every request due in
    ``[t0, t1)``; one with no token by ``t1`` counts at its age then."""
    out = []
    for r in records:
        if not t0 <= r.due < t1:
            continue
        first = r.tok_times[0] if r.tok_times else math.inf
        out.append(min(first, t1) - r.due)
    return out


def inter_token_gaps(records: Sequence[Record], t0: float,
                     t1: float) -> List[float]:
    """Every gap between two consecutive tokens of one request, both of
    which arrived inside ``[t0, t1]``."""
    out = []
    for r in records:
        ts = [t for t in r.tok_times if t0 <= t <= t1]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def tokens_in_window(records: Sequence[Record], t0: float,
                     t1: float) -> int:
    return sum(1 for r in records for t in r.tok_times if t0 < t <= t1)
