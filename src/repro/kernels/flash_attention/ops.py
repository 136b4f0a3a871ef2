"""jit'd public wrapper for flash attention (layout adapter + dispatch).

Models hold (B, S, H, hd); the kernel wants (B, H, S, hd).
``interpret=True`` executes the kernel body in Python on the CPU (the
tests); the default compiles it for the TPU.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention.flash_attention import flash_attention_fwd


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "block_q", "block_k",
                     "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    block_q=128, block_k=128, interpret=False):
    """q: (B, Sq, H, hd); k, v: (B, Sk, Hkv, hd) -> (B, Sq, H, hd)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention_fwd(qt, kt, vt, causal=causal, window=window,
                              q_offset=q_offset, block_q=block_q,
                              block_k=block_k, interpret=interpret)
    return out.transpose(0, 2, 1, 3)
