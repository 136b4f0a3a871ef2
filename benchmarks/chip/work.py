"""Operations and bytes that the algorithm needs for one dispatch: the
rules every architecture's counts keep (``models/<model_type>.py``
provides ``counts(config)``, whose ``decode_work(contexts)`` and
``chunk_work(rows)`` give (FLOPs, bytes)), their shared arithmetic, and
the chip's peaks.

Counted from the model's published shapes and each row's resident
context, never from how the program computes it:

* FLOPs count the experts a token is routed to (top-k), not all of them
  (the matmul arithmetic of ``roofline/flops.py::_per_token_matmul_flops``);
  attention counts each query against the keys it may see (causal), and
  the LM head only where a next token is due;
* KV bytes count each row's resident context, read once, and the new
  tokens written once, not the width of its block table;
* weight bytes count every weight the dispatch needs, read once: for a
  mixture of experts, the experts that ``T`` tokens are expected to touch,
  ``E * (1 - (1 - k/E) ** T)`` of them in each layer.

So a faster kernel raises a share honestly, and no share can pass 100%
unless the time leaves out part of the work.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Tuple

PEAKS_FILE = Path(__file__).with_name("peaks.json")
BF16 = 2


def keys_seen(pos0: int, n: int, window: int | None = None) -> int:
    """Keys that ``n`` queries at positions ``pos0 .. pos0 + n - 1`` see
    under the causal mask, each its own position's included: ``p + 1``
    for the query at ``p``, or at most ``window`` with a window."""
    end = pos0 + n
    cut = end if window is None else min(max(window - 1, pos0), end)
    return ((cut * (cut + 1) - pos0 * (pos0 + 1)) // 2
            + (end - cut) * (window or 0))


def load_peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path.name}; known: {sorted(table)}")
    return table[device_kind]


def least_time(flops: float, nbytes: float, peaks: dict
               ) -> Tuple[float, str]:
    """The least seconds the chip could take, and which bound sets it."""
    tc = flops / peaks["bf16_flops"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
