"""Blocked online-softmax attention (flash attention) for TPU.

Grid (B, H, nq, nk) — the kv index is minor-most so TPU grid iteration
visits all kv blocks of one (b, h, iq) consecutively; running max / sum /
accumulator live in VMEM scratch across those steps and the output block is
emitted at the last kv step. MXU-friendly block shapes (multiples of 128 on
the contracting dims at production sizes; the interpret-mode tests also
sweep smaller shapes).

GQA is free: the k/v BlockSpec index_map folds the query head onto its kv
group (h -> h * Hkv // H), so kv heads are never materialized per q head.
Causal + sliding-window masking via absolute positions (q_offset supports
decode/prefill-continuation).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int, q_offset: int,
                  block_q: int, block_k: int, nk: int):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                                   # (bq, hd)
    k = k_ref[0, 0]                                   # (bk, hd)
    v = v_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (bq, bk)

    iq = pl.program_id(2)
    q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    ok = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        ok &= k_pos <= q_pos
    if window > 0:
        ok &= k_pos > q_pos - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1)
    acc_scr[...] = (acc_scr[...] * corr[:, None]
                    + jax.lax.dot_general(
                        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ik == nk - 1)
    def _emit():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "block_q", "block_k",
                     "interpret"))
def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        q_offset: int = 0, block_q: int = 128,
                        block_k: int = 128, interpret: bool = False):
    """q: (B, H, Sq, hd); k, v: (B, Hkv, Sk, hd) with H % Hkv == 0.
    Returns (B, H, Sq, hd)."""
    B, H, Sq, hd = q.shape
    _, Hkv, Sk, _ = k.shape
    assert H % Hkv == 0, (H, Hkv)
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / math.sqrt(hd)
    grid = (B, H, nq, nk)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, block_q=bq, block_k=bk, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, iq, ik, Hkv_=Hkv, H_=H:
                         (b, h * Hkv_ // H_, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, iq, ik, Hkv_=Hkv, H_=H:
                         (b, h * Hkv_ // H_, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
