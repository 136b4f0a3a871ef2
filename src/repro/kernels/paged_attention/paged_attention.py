"""Paged-attention decode kernel for TPU (q-blocks of K >= 1 queries).

The serving KV cache is a global pool of fixed-size blocks (``serve/
block_pool.py``); each in-flight request owns a *block table* — the list
of pool blocks holding its tokens in order. Decode attention therefore
cannot stream K/V contiguously the way ``kernels/flash_attention`` does:
the kv blocks of one request are scattered across the pool.

The pool stays in HBM (``memory_space=pltpu.HBM``) and the kernel copies
the pages a row needs into VMEM itself, ``ppb`` pages of K and of V per step
(``pltpu.make_async_copy``), double-buffered: step ``s + 1``'s pages are
in flight while step ``s`` computes, and a row's last step starts the
next row's first. The block tables, lengths and the sliding window ride
in SMEM ahead of the grid (scalar prefetch). Grid is (B,); each row runs
an in-kernel loop over only the steps that hold its live pages:

* pages at or past ``ceil(lengths[b] / bs)`` are never copied and cost
  no step, so the work follows the row's length, not its table's width;
* a row with ``lengths[b] == 0`` (a parked row of a serving engine) reads
  nothing and emits zeros;
* a ``-1`` table entry is never copied, and its tokens are masked;
* with a window, pages wholly before every query's window are skipped.

A page holds every kv head, ``(bs, Gs, hd)``; ``ppb`` is chosen from
``bs`` so that a step moves about 256 tokens. GQA needs no repeat: the
wrapper holds q as ``(Gs, R·K, hd)`` (R = H / Gs query heads per kv head)
and both dots batch over the kv-head axis. Scores, the running max and
sum and the accumulator are float32; ``p`` is cast to V's dtype for the
PV dot.

Because block ``i`` of a table holds the request's tokens ``[i*bs,
(i+1)*bs)``, positions are structural: masking needs only ``lengths``
(and the window over absolute positions). The query is a q-block of K
tokens per request: query ``j`` of row ``b`` sits at absolute position
``lengths[b] - K + j``, its own k/v already resident (teacher-forced
verify writes the draft rows before dispatching, DESIGN.md §14); a
single decode query is the K = 1 block. Causality within the q-block is
the structural mask ``tok <= qpos``.

Contract: each live row has ``lengths[b] >= K``; the pages a live row
reads hold finite values.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
STEP_TOKENS = 256          # tokens of K (and of V) one grid step copies


def pages_per_step(block_size: int) -> int:
    """Pages one step copies: about ``STEP_TOKENS`` tokens of the pool."""
    return max(1, STEP_TOKENS // block_size)


def pages_in_place(kv_heads: int, head_dim: int) -> bool:
    """Whether the kernel can copy a pool page of ``kv_heads`` x
    ``head_dim`` whole: the TPU lays it out in (8, 128) tiles, and
    Mosaic cannot slice a page that only part of a tile holds."""
    return head_dim % 128 == 0 and (kv_heads == 1 or kv_heads % 8 == 0)


def _row_span(lengths_ref, window_ref, row, *, K, bs, ppb):
    """Pages ``[lo_page, npages)`` that ``row`` reads, and the steps
    ``[lo, hi)`` that hold them."""
    length = lengths_ref[row]
    npages = (length + bs - 1) // bs
    w = window_ref[0]
    first = jnp.where(w > 0, jnp.maximum(length - K - w + 1, 0), 0)
    lo_page = first // bs
    return lo_page, npages, lo_page // ppb, (npages + ppb - 1) // ppb


def _heads_major(pages, gs):
    """(ppb, bs, [Gs,] hd) pages -> (Gs, ppb·bs, hd)."""
    if pages.ndim == 3:                          # one kv head, squeezed
        return pages.reshape(-1, pages.shape[-1])[None]
    return pages.reshape(-1, gs, pages.shape[-1]).transpose(1, 0, 2)


def _paged_kernel(tables_ref, lengths_ref, window_ref, q_ref, k_hbm, v_hbm,
                  o_ref, kbuf, vbuf, sems, carry_ref, *, K: int, R: int,
                  ppb: int, scale: float, softcap: float):
    """One row. q block (1, Gs, K·R, hd), row ``k·R + r`` of kv head ``g``
    being query head ``g·R + r`` of query ``k``; kbuf/vbuf the two
    buffers of ``ppb`` pages, (2, ppb, bs, Gs, hd) or, for one kv head,
    (2, ppb, bs, hd); carry_ref (SMEM) holds the buffer of the next
    row's first step and whether that step is already in flight."""
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    _, gs, _, hd = q_ref.shape
    bs = kbuf.shape[2]
    T = ppb * bs
    M = K * R
    span = functools.partial(_row_span, lengths_ref, window_ref, K=K, bs=bs,
                             ppb=ppb)

    @pl.when(b == 0)
    def _init():
        carry_ref[0] = 0
        carry_ref[1] = 0

    def live(row, page, lo_page, npages):
        return ((page >= lo_page) & (page < npages)
                & (tables_ref[row, page] >= 0))

    def copies(row, page, slot, j):
        blk = tables_ref[row, page]
        return (pltpu.make_async_copy(k_hbm.at[blk], kbuf.at[slot, j],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[blk], vbuf.at[slot, j],
                                      sems.at[1, slot]))

    def start(row, step, slot, lo_page, npages):
        for j in range(ppb):
            page = step * ppb + j
            ok = live(row, page, lo_page, npages)

            @pl.when(ok)
            def _copy():
                for c in copies(row, page, slot, j):
                    c.start()

            @pl.when(jnp.logical_not(ok))
            def _blank():
                # p is 0 on a page that is not read, but 0 * NaN is not
                vbuf[slot, j] = jnp.zeros(vbuf.shape[2:], vbuf.dtype)

    def wait(row, step, slot, lo_page, npages):
        for j in range(ppb):
            page = step * ppb + j

            @pl.when(live(row, page, lo_page, npages))
            def _wait():
                for c in copies(row, page, slot, j):
                    c.wait()

    lo_page, npages, lo, hi = span(b)
    length = lengths_ref[b]
    w = window_ref[0]
    slot0 = carry_ref[0]

    @pl.when((hi > lo) & (carry_ref[1] == 0))
    def _first():
        start(b, lo, slot0, lo_page, npages)

    # this row's last step starts the next row's first, if both have one
    nxt = jnp.minimum(b + 1, rows - 1)
    n_lo_page, n_npages, n_lo, n_hi = span(nxt)
    handoff = (hi > lo) & (b + 1 < rows) & (n_hi > n_lo)

    q = q_ref[0]                                         # (Gs, M, hd)
    # query row k·R + r is query k, at absolute position length - K + k
    m_idx = jax.lax.broadcasted_iota(jnp.int32, (M, T), 0)
    k_of = sum((m_idx >= j * R).astype(jnp.int32) for j in range(1, K)) \
        if K > 1 else 0
    qpos = length - K + k_of
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)

    def body(step, carry):
        m, l, acc = carry
        slot = (slot0 + step - lo) % 2

        @pl.when(step + 1 < hi)
        def _prefetch_own():
            start(b, step + 1, 1 - slot, lo_page, npages)

        @pl.when((step + 1 == hi) & handoff)
        def _prefetch_next_row():
            start(nxt, n_lo, 1 - slot, n_lo_page, n_npages)

        wait(b, step, slot, lo_page, npages)

        k = _heads_major(kbuf[slot], gs)                    # (Gs, T, hd)
        v = _heads_major(vbuf[slot], gs)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale     # (Gs, M, T)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        # tokens are structural: page j of step s holds tokens from
        # (s·ppb + j)·bs; a page with no table entry is masked
        page_ok = jnp.zeros((1, T), jnp.bool_)
        for j in range(ppb):
            page_ok |= ((t_idx >= j * bs) & (t_idx < (j + 1) * bs)
                        & (tables_ref[b, step * ppb + j] >= 0))
        tok = step * T + t_idx
        ok = (tok <= qpos) & page_ok & ((w <= 0) | (tok > qpos - w))
        s = jnp.where(ok[None], s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)             # (Gs, M, hd)
        return m_new, l_new, acc_new

    m0 = jnp.full((gs, M, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((gs, M, 1), jnp.float32)
    acc0 = jnp.zeros((gs, M, hd), jnp.float32)
    _, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, acc0))
    carry_ref[0] = (slot0 + hi - lo) % 2
    carry_ref[1] = handoff.astype(jnp.int32)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_attention_fwd(q, k_pages, v_pages, block_tables, lengths,
                        window=0, *, softcap: float = 0.0,
                        interpret=False):
    """q: (B, H, hd) single-query, or (B, K, H, hd) q-block (query j of
    row b at absolute position ``lengths[b] - K + j``); k_pages, v_pages:
    (P, bs, Hkv, hd) with H % Hkv == 0; block_tables: (B, NB) int32
    (-1 = absent); lengths: (B,) int32 (0 = parked: zeros out); window:
    int32 scalar, traced or not (0 = none). Returns the same rank as q.

    A single query runs as the K = 1 q-block."""
    single = q.ndim == 3
    if single:
        q = q[:, None]
    B, K, H, hd = q.shape
    P, bs, Hkv, _ = k_pages.shape
    assert H % Hkv == 0, (H, Hkv)
    R = H // Hkv
    NB = block_tables.shape[1]
    ppb = min(pages_per_step(bs), NB)
    steps = -(-NB // ppb)
    tables = jnp.pad(block_tables.astype(jnp.int32),
                     ((0, 0), (0, steps * ppb - NB)), constant_values=-1)
    if Hkv == 1:
        # XLA lays a pool with one kv head out with that axis major, so
        # this is a bitcast; a (bs, 1, hd) page would be padded in VMEM
        k_pages = k_pages.reshape(P, bs, hd)
        v_pages = v_pages.reshape(P, bs, hd)
    page = k_pages.shape[1:]
    # (B, K, Gs, R, hd) -> (B, Gs, K·R, hd): one q row per (query, head)
    qg = q.reshape(B, K, Hkv, R, hd).transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, K * R, hd)

    kernel = functools.partial(
        _paged_kernel, K=K, R=R, ppb=ppb, scale=1.0 / math.sqrt(hd),
        softcap=softcap)
    q_spec = pl.BlockSpec((1, Hkv, K * R, hd), lambda b, *_: (b, 0, 0, 0))
    pool = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[q_spec, pool, pool],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((2, ppb) + page, k_pages.dtype),
                        pltpu.VMEM((2, ppb) + page, v_pages.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((2,), jnp.int32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, K * R, hd), q.dtype),
        # the next row's first pages are copied during this row's last
        # step, so rows run in order on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tables, lengths.astype(jnp.int32),
      jnp.asarray(window, jnp.int32).reshape(1), qg, k_pages, v_pages)
    out = out.reshape(B, Hkv, K, R, hd).transpose(0, 2, 1, 3, 4).reshape(
        B, K, H, hd)
    return out[:, 0] if single else out
