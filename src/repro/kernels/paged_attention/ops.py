"""Public entry point for paged attention.

The serving engine holds decode queries as (B, 1, H, hd) rows and the
block pool as (P, bs, Gs, hd). A (B, K, H, hd) query with K > 1 is a
speculative-verify q-block (query j at absolute position
``lengths[b] - K + j``); (B, H, hd) is a single query per row. Every form
runs the same q-block kernel. ``interpret=pltpu.InterpretParams()`` runs
the kernel, its DMAs included, in the TPU interpreter on the CPU (the
tests); the default compiles it for the TPU.
"""

from __future__ import annotations

from repro.kernels.paged_attention.paged_attention import paged_attention_fwd


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    window=0, softcap: float = 0.0, interpret=False):
    """q: (B, H, hd) or (B, K, H, hd); k_pages, v_pages: (P, bs, Hkv, hd);
    block_tables: (B, NB) int32; lengths: (B,) int32 (0 = parked row);
    window: int32 scalar, may be traced (0 = none) -> same rank as q."""
    return paged_attention_fwd(q, k_pages, v_pages, block_tables, lengths,
                               window, softcap=softcap, interpret=interpret)
