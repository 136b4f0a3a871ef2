"""jit'd wrapper + protocol dispatch for the msgq kernels.

Selects eager (VMEM-staged, 2 copies) vs 1-copy (direct) by message size,
using the paper's interthread threshold. ``copy_accounting`` reports the
bytes each protocol moves — the quantity behind the Fig.3 bandwidth curves.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax.numpy as jnp

from repro.core import protocol
from repro.kernels.msgq import msgq


def _pad_to(x, m):
    pad = (-x.size) % m
    return (jnp.pad(x, (0, pad)), pad)


def msgq_copy(msg, *, force_protocol: Optional[str] = None,
              cell_elems: int = 1024, interpret: bool = False):
    """Copy a message through the selected protocol. msg: any shape."""
    flat = msg.reshape(-1)
    nbytes = flat.size * flat.dtype.itemsize
    proto = (protocol.validate_protocol(force_protocol) if force_protocol
             else protocol.select_protocol(
                 nbytes, cell=cell_elems * flat.dtype.itemsize))
    if proto in ("eager_fast", "eager"):
        padded, pad = _pad_to(flat, cell_elems)
        out = msgq.eager_copy(padded, cell_elems=cell_elems,
                              interpret=interpret)
    else:
        block = min(65536, max(256, 1 << (flat.size - 1).bit_length()))
        padded, pad = _pad_to(flat, block)
        out = msgq.one_copy(padded, block_elems=block, interpret=interpret)
    if pad:
        out = out[:flat.size]
    return out.reshape(msg.shape), proto


def copy_accounting(nbytes: int, proto: str,
                    cell_bytes: int = 4096) -> Dict[str, float]:
    """Bytes moved / DMA issues per protocol (feeds bench_p2p)."""
    ncells = -(-nbytes // cell_bytes)
    if proto in ("eager_fast", "eager"):
        return {"bytes_moved": 2.0 * nbytes, "dma_issues": 2 * ncells,
                "staging_bytes": min(nbytes, cell_bytes)}
    return {"bytes_moved": float(nbytes), "dma_issues": ncells,
            "staging_bytes": 0.0}
