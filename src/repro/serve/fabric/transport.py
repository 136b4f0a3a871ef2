"""KV-block migration transport (DESIGN.md §10): request-based,
block-by-block handoff of a finished prefill's KV between two paged
pools.

This is the fabric's p2p hop, run under the paper's rendezvous
discipline end to end:

* the decode rank leases its destination blocks *first*
  (``ContinuousEngine.begin_import`` — the posted receive), so the
  lease is handed off rather than the prefill recomputed;
* the prompt's KV then crosses **one block per message**: each hop is a
  donated scatter of one source block into one destination block (a
  device-to-device copy when the ranks own different chips),
  serialized on a dedicated ``CommStream`` per destination device
  (``kv-migrate:<device id>``) and wrapped
  in a :class:`~repro.core.comm.Request` carrying the protocol model's
  request-object overhead for a message of one block — the exact
  ``isend``/``irecv`` pattern, with ``waitall`` as the completion point
  before the decode rank may touch the migrated state;
* the whole migration is priced by
  :func:`repro.core.protocol.kv_migration_latency` (one rendezvous
  handshake + per-block protocol-selected messages) and the modeled
  cost is stamped on the request for the bench artifact's
  ``kv_migration`` rows.

Bounding every message at one block is what keeps the fabric's decode
ranks responsive: a 2048-token prompt never crosses as one multi-MB
payload that would stall the receiving stream, it crosses as 128
independent block messages the stream interleaves like any other
traffic.
"""

from __future__ import annotations

import time
from typing import List

import jax
import jax.numpy as jnp

from repro.analysis.sanitizer import active as _san_active
from repro.core import protocol
from repro.core.comm import Request, waitall
from repro.obs.trace import active as _tr_active


class KVBlockTransport:
    """Block-by-block KV migration between two ``PagedKVCache`` pools."""

    def __init__(self, comm, stream_name: str = "kv-migrate"):
        self.comm = comm
        self.stream_name = stream_name
        #: one migrate stream per destination device: a stream's program
        #: order is a token array, which lives where its last block landed
        self._streams = {}
        # one compiled program per side for every hop: scalar block ids;
        # the destination pool is donated so XLA aliases it across the
        # chain
        self._take = jax.jit(self._take_impl)
        self._put = jax.jit(self._put_impl, donate_argnums=(0,))
        # accounting for the bench artifact's kv_migration rows
        self.n_migrations = 0
        self.n_blocks_moved = 0
        self.bytes_moved = 0
        self.modeled_cost_s = 0.0

    @staticmethod
    def _take_impl(src, src_block):
        """Source block ``src_block`` of every (L, P, bs, Gs, hd) leaf."""
        return jax.tree_util.tree_map(
            lambda s: jax.lax.dynamic_slice_in_dim(s, src_block, 1, axis=1),
            src)

    @staticmethod
    def _put_impl(dst, block, dst_block):
        """Scatter one taken block into destination block ``dst_block``.
        Also returns a 1-element completion probe read back out of the
        written block — the probe, not the pool, is what joins the
        stream and rides the Request (gating the full pool through an
        eager optimization_barrier would copy the whole un-donated pool
        once per block)."""
        new = jax.tree_util.tree_map(
            lambda d, b: jax.lax.dynamic_update_slice_in_dim(
                d, b.astype(d.dtype), dst_block, axis=1),
            dst, block)
        first = jax.tree_util.tree_leaves(new)[0]
        probe = jnp.ravel(jax.lax.dynamic_index_in_dim(
            first, dst_block, axis=1))[:1]
        return new, probe

    def _copy(self, dst, src, src_block, dst_block):
        """One block message: take the block on the source pool's device,
        move it to the destination pool's device (a device-to-device copy
        when the two ranks own different chips), scatter it there."""
        block = self._take(src, src_block)
        block = jax.device_put(
            block, jax.tree_util.tree_leaves(dst)[0].sharding)
        return self._put(dst, block, dst_block)

    def _stream_to(self, dst_kv):
        dev = next(iter(jax.tree_util.tree_leaves(dst_kv.buffers)[0]
                        .devices()))
        if dev not in self._streams:
            self._streams[dev] = self.comm.stream(
                f"{self.stream_name}:{dev.id}")
        return self._streams[dev]

    @staticmethod
    def block_nbytes(kv) -> int:
        """Bytes one pool block carries across all layers and both of
        k/v — the per-message payload size protocol selection sees."""
        return int(sum(leaf.nbytes // leaf.shape[1]
                       for leaf in jax.tree_util.tree_leaves(kv.buffers)))

    def migrate(self, src_kv, dst_kv, src_blocks: List[int],
                dst_blocks: List[int]) -> float:
        """Stream ``src_blocks`` of ``src_kv`` into ``dst_blocks`` of
        ``dst_kv`` (1:1, table order), one Request per block, and wait
        them all. Returns the modeled migration latency (seconds); the
        measured side effect is ``dst_kv``'s pool holding the prompt KV.
        """
        if len(src_blocks) != len(dst_blocks):
            raise ValueError(
                f"block lists disagree: {len(src_blocks)} source vs "
                f"{len(dst_blocks)} destination")
        if src_kv.block_size != dst_kv.block_size:
            raise ValueError(
                f"pools disagree on block_size: {src_kv.block_size} vs "
                f"{dst_kv.block_size} (1:1 block migration needs equal "
                "token geometry)")
        nb = self.block_nbytes(src_kv)
        proto = protocol.select_protocol(nb, interthread=True)
        stream = self._stream_to(dst_kv)
        requests: List[Request] = []
        dst = dst_kv.buffers
        san = _san_active()
        if san is not None:
            san.on_migrate_begin(self, len(src_blocks))
        tr = _tr_active()
        t_xfer = time.perf_counter() if tr is not None else 0.0
        # the first hop donates the live destination pool, so from here
        # on dst_kv MUST end up pointing at the freshest chain value
        # whatever happens — on an error mid-chain or at completion the
        # old buffers are already gone, and leaving dst_kv on them would
        # crash every later decode step with a deleted-array error that
        # masks the real failure
        try:
            for sb, db in zip(src_blocks, dst_blocks):
                dst, probe = self._copy(dst, src_kv.buffers,
                                        jnp.int32(sb), jnp.int32(db))
                # the probe (read out of the freshly written block,
                # inside the same jit) joins the migrate stream's
                # program order — MPIX-stream serialization of the
                # per-block sends — and rides the Request whose wait()
                # is the block's completion point; the pool itself is
                # serialized by the donation chain and must not be
                # pinned by a request (the next hop deletes it)
                probe = stream.ordered(probe)
                requests.append(Request(
                    self.comm, f"kv_block[{proto}]", probe,
                    stream=stream,
                    model_overhead_s=protocol.request_overhead(nb, proto)))
        finally:
            # request-leak: completion must sit on the exception path
            # too — an error mid-chain used to abandon every block
            # message already in flight (their Requests died unwaited at
            # the next finish()); the issued prefix is always valid, so
            # complete it before the pool install either way
            try:
                waitall(requests)          # completion before install
                if san is not None and len(requests) == len(src_blocks):
                    san.on_migrate_end(self)
            finally:
                dst_kv.swap_buffers(dst)
        moved = len(src_blocks)
        # the model already charges each block's request object inside
        # its per-block message price — the Request.model_overhead_s
        # fields are the per-message view of the same cost, not an add-on
        cost = protocol.kv_migration_latency(moved * nb, nb)
        if tr is not None:
            # the pure block-transfer span; it nests (by timestamp
            # containment) inside the router's hop:migration event,
            # which also covers the lease import bookkeeping
            tr.complete("kv_transfer", t_xfer, time.perf_counter(),
                        cat="fabric", blocks=moved)
        self.n_migrations += 1
        self.n_blocks_moved += moved
        self.bytes_moved += moved * nb
        self.modeled_cost_s += cost
        return cost

    def stats(self) -> dict:
        """Aggregate migration accounting for the bench artifact."""
        return {
            "n_migrations": float(self.n_migrations),
            "blocks_moved": float(self.n_blocks_moved),
            "bytes_moved": float(self.bytes_moved),
            "kv_migration_modeled_s": self.modeled_cost_s,
            "kv_migration_us_per_block":
                (1e6 * self.modeled_cost_s / self.n_blocks_moved
                 if self.n_blocks_moved else 0.0),
        }

    def reset(self) -> None:
        self.n_migrations = 0
        self.n_blocks_moved = 0
        self.bytes_moved = 0
        self.modeled_cost_s = 0.0
