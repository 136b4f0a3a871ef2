"""End-to-end metrics of one measured window, from request records.

Every time is in seconds on the harness's clock. A request is *due* when
the traffic asks for it; its time to first token runs from then, so a
stalled generator or a full queue shows as latency. Copied from the
scheduler's ``latency_stats_over`` arithmetic and extended with a window:
work and tails are taken over everything the window saw, finished or not.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import numpy as np


@dataclass
class Record:
    """What the harness saw of one request."""
    rid: int
    prompt_len: int
    max_new: int
    due: float
    first: Optional[float] = None         # first token seen by the harness
    finish: Optional[float] = None
    generated: int = 0                    # tokens at the last look
    gen_at_start: int = 0                 # tokens when the window opened
    gen_at_end: int = 0                   # tokens when the window closed
    failed: bool = False                  # refused or errored


def p95(values) -> Optional[float]:
    values = np.asarray(list(values), float)
    return float(np.percentile(values, 95)) if values.size else None


def due_in(recs: List[Record], t0: float, t1: float) -> List[Record]:
    return [r for r in recs if t0 <= r.due < t1]


def out_tok_s(recs: List[Record], t0: float, t1: float) -> float:
    """Output tokens made inside [t0, t1], in every request, per second."""
    return sum(r.gen_at_end - r.gen_at_start for r in recs) / (t1 - t0)


def ttft_s(recs: List[Record], t0: float, t1: float) -> List[float]:
    """Time to first token of each request due in the window; one with no
    first token by ``t1`` (failed ones too) counts as ``t1 - due``."""
    out = []
    for r in due_in(recs, t0, t1):
        if r.failed or r.first is None or r.first > t1:
            out.append(t1 - r.due)
        else:
            out.append(r.first - r.due)
    return out


def tpot_ms(recs: List[Record], t0: float, t1: float) -> List[float]:
    """Time per output token after the first, of each request finished in
    the window with at least two tokens, in milliseconds."""
    return [1e3 * (r.finish - r.first) / (r.generated - 1) for r in recs
            if r.finish is not None and t0 <= r.finish <= t1
            and r.first is not None and r.generated >= 2]


def window_metrics(recs: List[Record], t0: float, t1: float) -> dict:
    """Every end-to-end number of the window except ``setup_s``."""
    due = due_in(recs, t0, t1)
    return {
        "out_tok_s": out_tok_s(recs, t0, t1),
        "ttft_p95_s": p95(ttft_s(recs, t0, t1)),
        "tpot_p95_ms": p95(tpot_ms(recs, t0, t1)),
        "attempted": len(due),
        "failed": sum(r.failed for r in due),
    }
