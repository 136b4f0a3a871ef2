"""Paged-attention decode kernel for TPU (q-blocks of K >= 1 queries).

The serving KV cache is a global pool of fixed-size blocks (``serve/
block_pool.py``); each in-flight request owns a *block table* — the list
of pool blocks holding its tokens in order. Decode attention therefore
cannot stream K/V contiguously the way ``kernels/flash_attention`` does:
the kv blocks of one request are scattered across the pool.

This kernel gathers them through the table with *scalar prefetch*
(``pltpu.PrefetchScalarGridSpec``): the block tables and lengths ride in
SMEM ahead of the grid, and the k/v BlockSpec index maps read
``tables[b, i]`` to aim the automatic HBM→VMEM pipeline at the right
pool block — the gather costs no extra copies, it *is* the pipeline.
Grid is (B, NB) with the table index minor-most, so the running
max / sum / accumulator of the online softmax live in VMEM scratch
across one request's blocks and the output is emitted on the last one
(same discipline as the flash kernel).

Because block ``i`` of a table holds the request's tokens
``[i*bs, (i+1)*bs)``, positions are structural — no per-token position
array is gathered; masking needs only ``lengths`` (and the optional
sliding window over absolute positions). GQA is free the same way as in
flash attention: kv heads are repeated only inside VMEM, never
rematerialized in HBM.

The query is a q-block of K tokens per request: q is ``(B, K, H, hd)``
and query ``j`` of row ``b`` sits at absolute position
``lengths[b] - K + j``, its own k/v already resident (teacher-forced
verify writes the draft rows before dispatching, DESIGN.md §14). A
single decode query is the K = 1 block. Causality *within* the q-block
is a per-query structural mask ``tok <= qpos`` — no mask tensor is
gathered, and the online-softmax scratch carries a K axis ((H, K)
running max/sum, (H, K, hd) accumulator).

Contract: each live row has ``lengths[b] >= K`` and a valid
``tables[b, 0]``. Rows with an all ``-1`` table (parked decode rows of a
serving engine) produce finite garbage that the caller must discard —
their pool writes were dropped upstream, so no live data is at risk.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, window: int,
                  softcap: float, block_size: int, nb: int):
    """q block (1, K, H, hd); query j of row b is the token at absolute
    position ``lengths[b] - K + j``."""
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                     # (K, H, hd)
    k = k_ref[0]                                     # (bs, Hkv, hd)
    v = v_ref[0]
    K, H, _ = q.shape
    hkv = k.shape[1]
    if hkv != H:                                     # GQA: repeat in VMEM only
        k = jnp.repeat(k, H // hkv, axis=1)
        v = jnp.repeat(v, H // hkv, axis=1)
    s = jax.lax.dot_general(
        q.transpose(1, 0, 2), k.transpose(1, 0, 2),
        (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale  # (H, K, bs)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)

    # token positions are structural: table entry i holds [i*bs, (i+1)*bs);
    # query j sits at absolute position length - K + j
    tok = i * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (K, block_size), 1)
    length = lengths_ref[b]
    qpos = (length - K) + jax.lax.broadcasted_iota(
        jnp.int32, (K, block_size), 0)
    ok = (tok <= qpos) & (tables_ref[b, i] >= 0)
    if window > 0:
        ok &= tok > qpos - window
    s = jnp.where(ok[None], s, NEG_INF)

    m_prev = m_scr[...]                              # (H, K)
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1)
    acc_scr[...] = (acc_scr[...] * corr[..., None]
                    + jax.lax.dot_general(
                        p.astype(v.dtype), v.transpose(1, 0, 2),
                        (((2,), (1,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32))
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(i == nb - 1)
    def _emit():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)[..., None]
                    ).transpose(1, 0, 2).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "interpret"))
def paged_attention_fwd(q, k_pages, v_pages, block_tables, lengths, *,
                        window: int = 0, softcap: float = 0.0,
                        interpret: bool = False):
    """q: (B, H, hd) single-query, or (B, K, H, hd) q-block (query j of
    row b at absolute position ``lengths[b] - K + j``); k_pages, v_pages:
    (P, bs, Hkv, hd) with H % Hkv == 0; block_tables: (B, NB) int32
    (-1 = absent); lengths: (B,) int32. Returns the same rank as q.

    A single query runs as the K = 1 q-block."""
    single = q.ndim == 3
    if single:
        q = q[:, None]
    B, K, H, hd = q.shape
    P, bs, Hkv, _ = k_pages.shape
    assert H % Hkv == 0, (H, Hkv)
    NB = block_tables.shape[1]
    scale = 1.0 / math.sqrt(hd)

    def kv_map(b, i, tables, lengths_):
        # absent entries clamp to block 0; the kernel masks them out
        return (jnp.maximum(tables[b, i], 0), 0, 0, 0)

    kernel = functools.partial(
        _paged_kernel, scale=scale, window=window, softcap=softcap,
        block_size=bs, nb=NB)
    q_spec = pl.BlockSpec((1, K, H, hd), lambda b, i, t, n: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, NB),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, bs, Hkv, hd), kv_map),
            pl.BlockSpec((1, bs, Hkv, hd), kv_map),
        ],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((H, K), jnp.float32),
                        pltpu.VMEM((H, K), jnp.float32),
                        pltpu.VMEM((H, K, hd), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, H, hd), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pages, v_pages)
    return out[:, 0] if single else out
