"""Cell-queue message copy kernels (paper §3.2, TPU adaptation).

The paper's interthread messaging moves a message through a bounded pool of
fixed-size shared-memory cells (eager, 2 copies) or directly from the sender
buffer (1-copy). The TPU analogue (DESIGN.md §2): the cell pool becomes a
bounded VMEM staging buffer and the 1-copy path a direct HBM→HBM block DMA.
The lockless-MPSC atomics do not transfer — Pallas grids are scheduled, not
racing — but the protocol structure (bounded cells / staging vs direct) and
its bandwidth consequences do.

Kernels:
  * eager_kernel:    per-cell staged copy through a VMEM scratch cell
                     (explicit second copy: src→cell, cell→dst).
  * one_copy_kernel: direct block copy, no staging scratch.
Both use explicit BlockSpec tiling; one cell/block per grid step.
"""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _eager_kernel(src_ref, dst_ref, cell_ref):
    # copy 1: message fragment -> staging cell (the shared-memory cell)
    cell_ref[...] = src_ref[...]
    # copy 2: cell -> receiver buffer (receiver consumes the cell)
    dst_ref[...] = cell_ref[...]


def _one_copy_kernel(src_ref, dst_ref):
    # receiver copies directly from the sender buffer (shared address space)
    dst_ref[...] = src_ref[...]


@functools.partial(jax.jit, static_argnames=("cell_elems", "interpret"))
def eager_copy(msg: jax.Array, *, cell_elems: int = 2048,
               interpret: bool = False) -> jax.Array:
    """Eager-protocol copy: message staged through one reused VMEM cell
    (the bounded cell pool). msg: 1-D, length multiple of cell_elems
    (ops.py pads)."""
    (n,) = msg.shape
    assert n % cell_elems == 0, (n, cell_elems)
    ncells = n // cell_elems
    return pl.pallas_call(
        _eager_kernel,
        grid=(ncells,),
        in_specs=[pl.BlockSpec((cell_elems,), lambda i: (i,))],
        out_specs=pl.BlockSpec((cell_elems,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct(msg.shape, msg.dtype),
        scratch_shapes=[pltpu.VMEM((cell_elems,), msg.dtype)],
        interpret=interpret,
    )(msg)


@functools.partial(jax.jit, static_argnames=("block_elems", "interpret"))
def one_copy(msg: jax.Array, *, block_elems: int = 65536,
             interpret: bool = False) -> jax.Array:
    """1-copy protocol: direct blocked DMA, no staging."""
    (n,) = msg.shape
    block = min(block_elems, n)
    assert n % block == 0, (n, block)
    nblocks = n // block
    return pl.pallas_call(
        _one_copy_kernel,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct(msg.shape, msg.dtype),
        interpret=interpret,
    )(msg)
