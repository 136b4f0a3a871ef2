"""Mamba2 SSD chunk-scan kernel (state-space duality, arXiv:2405.21060).

Grid (B, H, n_chunks) with the chunk index minor-most: the running SSM
state (head_dim × state) lives in VMEM scratch and is carried across the
sequential chunk steps of each (b, h) pair, reset at chunk 0. Each grid
step computes the intra-chunk quadratic (attention-like) term plus the
contribution of the carried state, then folds the chunk into the state —
the SSD blocked algorithm with O(l·p + p·n) VMEM per step.

Block shapes: chunk length l and head_dim p are the MXU-facing dims; at
production sizes use l=128/p=64-128 (multiples of the 128 lane width where
possible). ngroups=1 (all assigned configs): B/C blocks are shared across
heads via the index_map.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dtr_ref, dtc_ref, a_ref, b_ref, c_ref, s0_ref, y_ref,
                fs_ref, state_scr, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        # seed the carried state from the caller (zeros for a fresh
        # sequence; a previous call's final state to resume a chunked
        # prefill bit-exactly — DESIGN.md §13)
        state_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)        # (l, p)
    A = a_ref[pl.program_id(1)]                # scalar (negative), SMEM
    dA_row = dtr_ref[0, 0].astype(jnp.float32) * A   # (1, l)
    dt_col = dtc_ref[0, 0].astype(jnp.float32)       # (l, 1)
    dA_col = dt_col * A                              # (l, 1)
    Bm = b_ref[0, 0].astype(jnp.float32)       # (l, n)
    Cm = c_ref[0, 0].astype(jnp.float32)       # (l, n)

    # inclusive prefix sums of dA as a column and as a row: masked
    # reductions of the broadcast vector (Mosaic has no cumsum, and dt
    # arrives in both layouts so neither needs a transpose)
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = li >= lj
    cum_col = jnp.sum(jnp.where(lower, dA_row, 0.0), axis=1,
                      keepdims=True)                 # (l, 1)
    cum_row = jnp.sum(jnp.where(lower, 0.0, dA_col), axis=0,
                      keepdims=True) + dA_row        # (1, l)
    total = jnp.sum(dA_row, axis=1, keepdims=True)   # (1, 1)
    # lower-triangular decay matrix L[i,j] = exp(sum_{k=j+1..i} dA_k)
    Lm = jnp.where(lower, jnp.exp(cum_col - cum_row), 0.0)

    xdt = x * dt_col                           # (l, p)
    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * Lm          # (l, l)
    y_diag = jax.lax.dot_general(
        scores, xdt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (l, p)

    state = state_scr[...]                     # (p, n)
    # contribution of the carried state: exp(cum) * C @ state^T
    y_off = jax.lax.dot_general(
        Cm, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * jnp.exp(cum_col)

    # fold this chunk into the state
    decay_states = jnp.exp(total - cum_col)    # (l, 1)
    upd = jax.lax.dot_general(
        xdt, Bm * decay_states, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (p, n)
    new_state = state * jnp.exp(total) + upd
    state_scr[...] = new_state
    # every chunk writes the running state to the same output block —
    # the last (sequentially final) chunk's write is what survives
    fs_ref[0, 0] = new_state

    y_ref[0, 0] = (y_diag + y_off).astype(y_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret", "return_state"))
def ssd_scan_fwd(x, dt, A, Bm, Cm, initial_state=None, *, chunk: int = 128,
                 interpret: bool = False, return_state: bool = False):
    """x: (B,H,S,p); dt: (B,H,S) (post-softplus); A: (H,) negative;
    Bm, Cm: (B,S,n) (ngroups=1). Returns y (B,H,S,p).

    ``initial_state`` (B,H,p,n) f32 seeds the carried scan state (zeros
    when None — a fresh sequence); ``return_state=True`` additionally
    returns the final state, so a chunked prefill can resume the scan
    from exactly where the previous chunk stopped."""
    B, H, S, p = x.shape
    n = Bm.shape[-1]
    l = min(chunk, S)
    assert S % l == 0, (S, l)
    nc = S // l
    if initial_state is None:
        initial_state = jnp.zeros((B, H, p, n), jnp.float32)
    kernel = functools.partial(_ssd_kernel, chunk=l)
    y, final_state = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, l, p), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, 1, l), lambda b, h, ic: (b, h, 0, ic)),
            pl.BlockSpec((1, 1, l, 1), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, l, n), lambda b, h, ic: (b, 0, ic, 0)),
            pl.BlockSpec((1, 1, l, n), lambda b, h, ic: (b, 0, ic, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, l, p), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, p), x.dtype),
            jax.ShapeDtypeStruct((B, H, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x, dt.reshape(B, H, 1, S), dt.reshape(B, H, S, 1), A,
      Bm.reshape(B, 1, S, n), Cm.reshape(B, 1, S, n),
      initial_state.astype(jnp.float32))
    if return_state:
        return y, final_state
    return y
