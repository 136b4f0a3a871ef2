"""Paged KV substrate: a global pool of fixed-size KV blocks leased
through per-request block tables (DESIGN.md §9).

This generalizes the paper's cell pool one step further than
``SlotKVCache``: there, one cell = one whole request (a slot reserves
``cache_len`` tokens of HBM whether the request is 16 tokens or 2048);
here, one cell = one KV *block* of ``block_size`` tokens, and a request
leases exactly the blocks its tokens occupy — the MPIX-stream
progression from coarse process-level to fine stream-level resources
applied to serving memory. Pool capacity is then measured in bytes, not
request count: a 16-token request holds 1–2 blocks while a 2048-token
request holds 128, and admission gates on *free blocks* instead of free
slots.

Two layers:

* :class:`BlockPool` — the host-side allocator: O(1) free-list
  alloc/free, per-block reference counts (a block can back several
  requests sharing a prefix — the refcount is the mechanism; prefix
  sharing itself is a later consumer), owners recorded for error
  reporting. Misuse raises :class:`~repro.serve.kv_cache.SlotError`
  naming the owner, exactly like the slot pool.
* :class:`PagedKVCache` — the engine-facing cache: the device-side block
  pool pytree (``model.init_paged_cache``), a fixed set of *request
  rows* (the decode batch width), and one block table per row. Mirrors
  the ``SlotKVCache`` surface (alloc/free/advance/lengths/buffers/
  swap_buffers/reset) so the continuous engine can drive either layout.

Host-side length/refcount bookkeeping is uniformly ``np.int32`` — the
same dtype as device positions, so host→device table/length transfers
never silently widen (the slot pool's ``np.int64`` lengths were the odd
one out; both pools now agree).
"""

from __future__ import annotations

import warnings
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.sanitizer import active as _san_active
from repro.obs.metrics import active as _reg_active
from repro.serve.kv_cache import LeaseLeakError, LeaseLeakWarning, SlotError


class BlockPool:
    """O(1) free-list allocator over a fixed population of KV blocks."""

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 1:
            raise SlotError("need at least one block")
        if block_size < 1:
            raise SlotError("block_size must be >= 1")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._ref = np.zeros((num_blocks,), np.int32)
        self._owner: List[Optional[object]] = [None] * num_blocks
        self._last_owner: List[Optional[object]] = [None] * num_blocks
        self._reclaimer = None        # e.g. a PrefixCache (DESIGN.md §12)

    def attach_reclaimer(self, reclaimer) -> None:
        """Register a deferred reclaimer (the prefix cache): blocks it
        parks count as free for admission (``num_free``), ``alloc``
        asks it to ``reclaim`` when the free list runs short, and
        ``free`` notifies it when a block's sole surviving reference
        could be its own (``on_sole_ref``)."""
        if self._reclaimer is not None and self._reclaimer is not reclaimer:
            raise SlotError("pool already has a reclaimer attached")
        self._reclaimer = reclaimer

    @property
    def num_free(self) -> int:
        free = len(self._free)
        if self._reclaimer is not None:
            free += self._reclaimer.evictable()
        return free

    @property
    def num_live(self) -> int:
        return self.num_blocks - len(self._free)

    def refcount(self, block: int) -> int:
        return int(self._ref[block])

    def owner(self, block: int):
        return self._owner[block]

    def blocks_needed(self, ntokens: int) -> int:
        """Table entries a request of ``ntokens`` tokens occupies."""
        if ntokens < 0:
            raise SlotError(f"negative token count {ntokens}")
        return -(-int(ntokens) // self.block_size)

    def alloc(self, n: int, owner: object) -> List[int]:
        """Lease ``n`` blocks for ``owner`` (refcount 1 each). Raises on
        exhaustion — admission control must gate on ``num_free``."""
        if owner is None:
            raise SlotError("block owner must be non-None")
        if n > len(self._free) and self._reclaimer is not None:
            # deferred reclamation: evict parked prefix-cache blocks
            # (LRU order) until the free list covers the request
            self._reclaimer.reclaim(n - len(self._free))
        if n > len(self._free):
            raise SlotError(
                f"block pool exhausted: need {n}, have {len(self._free)} "
                "(admission must gate on num_free)")
        blocks = [self._free.pop() for _ in range(n)]
        for b in blocks:
            self._ref[b] = 1
            self._owner[b] = owner
            self._last_owner[b] = owner
        san = _san_active()
        if san is not None:       # lease ledger records the alloc site
            san.on_lease_alloc(self, blocks, owner)
        self._observe_occupancy()
        return blocks

    def ref(self, block: int, owner: object = None) -> None:
        """Add a reference to a live block (shared-prefix lease);
        ``owner`` feeds the ledger's shared-ref provenance."""
        if self._ref[block] < 1:
            raise SlotError(f"ref of free block {block}")
        self._ref[block] += 1
        san = _san_active()
        if san is not None:
            san.on_lease_ref(self, block, owner)

    def free(self, blocks) -> None:
        """Drop one reference per block; blocks reaching zero return to
        the free list. Double-free names the last owner."""
        san = _san_active()
        for b in blocks:
            if self._ref[b] < 1:
                msg = (f"double free of block {b} "
                       f"(last owner {self._last_owner[b]!r})")
                if san is not None:
                    # the ledger remembers where the block was first
                    # allocated and first freed — the half of the story
                    # the refcount alone can't tell
                    msg += "; " + san.on_double_free(
                        self, b, self._last_owner[b])
                raise SlotError(msg)
            self._ref[b] -= 1
            if self._ref[b] == 0:
                self._owner[b] = None
                self._free.append(b)
            elif self._ref[b] == 1 and self._reclaimer is not None:
                # the survivor may be the reclaimer's own reference —
                # it parks the block (LRU) if so, ignores otherwise
                self._reclaimer.on_sole_ref(b)
            if san is not None:
                san.on_lease_release(self, b)
        self._observe_occupancy()

    def _observe_occupancy(self) -> None:
        """Telemetry (DESIGN.md §15): block-pool occupancy as registry
        gauges, sampled at lease transitions (per request admission/
        finish, not per token — alloc/free are the only places occupancy
        moves). The engine's ``serve.step`` span carries ``blocks_free``."""
        reg = _reg_active()
        if reg is not None:
            reg.gauge("block_pool.free_blocks").set(len(self._free))
            reg.gauge("block_pool.live_blocks").set(
                self.num_blocks - len(self._free))

    def reset(self, *, strict: bool = False) -> None:
        """Wipe every lease. Blocks still live are leaks — requests that
        never reached ``free`` — and are named: warn
        (:class:`~repro.serve.kv_cache.LeaseLeakWarning`) by default,
        raise (:class:`~repro.serve.kv_cache.LeaseLeakError`) under
        ``strict=True``."""
        leaked = [(b, self._owner[b]) for b in range(self.num_blocks)
                  if self._ref[b] > 0]
        san = _san_active()
        if san is not None:       # ledger adds allocation provenance
            san.on_pool_reset(self)
        if leaked:
            msg = (f"reset with {len(leaked)} live block lease(s): "
                   + ", ".join(f"block {b} (owner {o!r})"
                               for b, o in leaked[:8])
                   + (f", ... {len(leaked) - 8} more" if len(leaked) > 8
                      else ""))
            if strict:
                raise LeaseLeakError(msg)
            warnings.warn(msg, LeaseLeakWarning, stacklevel=2)
        self._free = list(range(self.num_blocks - 1, -1, -1))
        self._ref[:] = 0
        self._owner = [None] * self.num_blocks
        if self._reclaimer is not None:
            # every lease (the reclaimer's included) was just wiped; the
            # reclaimer drops its index without re-freeing anything
            self._reclaimer.on_pool_reset()


class PagedKVCache:
    """Paged decode-state cache: fixed request rows + leased KV blocks.

    ``num_slots`` is the decode batch width (request rows) — cheap host
    state only; the expensive resource is the block pool, sized
    independently by ``num_blocks``. A request's admission cost is
    ``blocks_for(prompt + max_new)`` blocks (reserved up front, so a
    live request can never hit mid-decode exhaustion) plus one row.
    """

    def __init__(self, model, *, num_blocks: int, block_size: int,
                 num_slots: int, max_blocks_per_req: int):
        if num_slots < 1:
            raise SlotError("need at least one request row")
        if max_blocks_per_req < 1:
            raise SlotError("max_blocks_per_req must be >= 1")
        self.model = model
        self.num_slots = int(num_slots)
        self.block_size = int(block_size)
        self.max_blocks_per_req = int(max_blocks_per_req)
        self.pool = BlockPool(num_blocks, block_size)
        # num_rows sizes the row-aligned carried-state leaves (SSM
        # conv/ssm, enc-dec cross K/V) that ride in the same pytree as
        # the block-addressed k/v pool (DESIGN.md §13)
        self._buf = model.init_paged_cache(num_blocks, block_size,
                                           num_rows=num_slots)
        self._tables = np.full((num_slots, max_blocks_per_req), -1, np.int32)
        self._tables_dev = None       # host->device copy, built on demand
        self._free_rows: List[int] = list(range(num_slots - 1, -1, -1))
        self._owner: List[Optional[object]] = [None] * num_slots
        self._last_owner: List[Optional[object]] = [None] * num_slots
        self._nblocks = np.zeros((num_slots,), np.int32)
        # tokens resident per row — np.int32, same dtype as device positions
        self._len = np.zeros((num_slots,), np.int32)

    # -- pool / row accounting ---------------------------------------------
    @property
    def num_free(self) -> int:
        """Free request rows (the admission gate shared with the slot
        layout; block availability is the second, paged-only gate)."""
        return len(self._free_rows)

    @property
    def num_live(self) -> int:
        return self.num_slots - len(self._free_rows)

    @property
    def num_free_blocks(self) -> int:
        return self.pool.num_free

    @property
    def live_slots(self) -> List[int]:
        return [s for s in range(self.num_slots) if self._owner[s] is not None]

    def owner(self, slot: int):
        return self._owner[slot]

    def length(self, slot: int) -> int:
        return int(self._len[slot])

    @property
    def lengths(self) -> np.ndarray:
        return self._len.copy()

    def blocks_for(self, ntokens: int) -> int:
        return self.pool.blocks_needed(ntokens)

    def blocks_of(self, slot: int) -> List[int]:
        """The block ids leased to ``slot``, in table order (the
        migration transport copies these 1:1 into the destination
        lease)."""
        if self._owner[slot] is None:
            raise SlotError(f"blocks_of free row {slot}")
        return self._tables[slot, :int(self._nblocks[slot])].tolist()

    def can_admit(self, ntokens: int, hit=None) -> bool:
        """One free row + enough free blocks for ``ntokens`` tokens.

        With a :class:`~repro.serve.prefix_cache.PrefixHit`, only the
        *miss* tail needs fresh blocks — but the hit's parked blocks,
        while costing nothing from the free list, stop being evictable
        the moment they are leased, so they are subtracted from the
        pool's (free + evictable) headroom."""
        nb = self.blocks_for(ntokens)
        if nb > self.max_blocks_per_req:
            raise SlotError(
                f"request of {ntokens} tokens needs {nb} blocks > "
                f"max_blocks_per_req={self.max_blocks_per_req}")
        if not self._free_rows:
            return False
        if hit is None:
            return nb <= self.pool.num_free
        fresh = nb - len(hit.blocks)
        return fresh <= self.pool.num_free - hit.n_parked

    # -- lease lifecycle ---------------------------------------------------
    def alloc(self, owner: object, ntokens: int) -> int:
        """Claim a request row and lease the blocks ``ntokens`` tokens
        will occupy. Raises on row/block exhaustion."""
        if owner is None:
            raise SlotError("row owner must be non-None")
        if not self._free_rows:
            raise SlotError("request rows exhausted (admission must gate "
                            "on num_free)")
        nb = self.blocks_for(ntokens)
        if nb > self.max_blocks_per_req:
            raise SlotError(
                f"request of {ntokens} tokens needs {nb} blocks > "
                f"max_blocks_per_req={self.max_blocks_per_req}")
        blocks = self.pool.alloc(nb, owner)   # raises before row is taken
        slot = self._free_rows.pop()
        self._owner[slot] = owner
        self._last_owner[slot] = owner
        self._tables[slot, :] = -1
        self._tables[slot, :nb] = np.asarray(blocks, np.int32)
        self._tables_dev = None
        self._nblocks[slot] = nb
        self._len[slot] = 0
        return slot

    def alloc_prefix(self, owner: object, ntokens: int, hit,
                     cache) -> int:
        """Claim a row backed partly by cached prefix blocks: the hit's
        blocks are leased at refcount+1 through ``cache.lease`` (CoW
        source included, as a temporary reference) and only the miss
        tail is freshly allocated. Lease-before-alloc ordering matters:
        a reclaim triggered by the fresh allocation can never evict a
        block this request just hit."""
        if owner is None:
            raise SlotError("row owner must be non-None")
        if not self._free_rows:
            raise SlotError("request rows exhausted (admission must gate "
                            "on num_free)")
        nb = self.blocks_for(ntokens)
        if nb > self.max_blocks_per_req:
            raise SlotError(
                f"request of {ntokens} tokens needs {nb} blocks > "
                f"max_blocks_per_req={self.max_blocks_per_req}")
        shared = list(hit.blocks)
        cache.lease(hit, owner)
        try:
            fresh = self.pool.alloc(nb - len(shared), owner)
        except SlotError:
            # unwind the shared leases; admission should have gated
            if hit.cow_src is not None:
                self.pool.free([hit.cow_src])
            self.pool.free(shared)
            raise
        slot = self._free_rows.pop()
        self._owner[slot] = owner
        self._last_owner[slot] = owner
        self._tables[slot, :] = -1
        self._tables[slot, :nb] = np.asarray(shared + fresh, np.int32)
        self._tables_dev = None
        self._nblocks[slot] = nb
        self._len[slot] = 0
        return slot

    def free(self, slot: int) -> None:
        if self._owner[slot] is None:
            raise SlotError(
                f"double free of request row {slot} "
                f"(last owner {self._last_owner[slot]!r})")
        nb = int(self._nblocks[slot])
        self.pool.free(self._tables[slot, :nb].tolist())
        self._tables[slot, :] = -1
        self._tables_dev = None
        self._nblocks[slot] = 0
        self._owner[slot] = None
        self._len[slot] = 0
        self._free_rows.append(slot)

    def advance(self, slot: int, n: int = 1) -> None:
        """Account ``n`` more resident tokens in ``slot``. The lease
        already covers them (blocks are reserved at admission), so this
        is bookkeeping only — but overrunning the lease is a bug."""
        if self._owner[slot] is None:
            raise SlotError(f"advance on free row {slot}")
        new = int(self._len[slot]) + int(n)
        if new > int(self._nblocks[slot]) * self.block_size:
            raise SlotError(
                f"row {slot} (owner {self._owner[slot]!r}) overran its "
                f"lease: {new} tokens > {int(self._nblocks[slot])} blocks "
                f"x {self.block_size}")
        self._len[slot] = new

    # -- tables / buffers --------------------------------------------------
    def table_rows(self, slots) -> np.ndarray:
        """(len(slots), max_blocks_per_req) int32 view copies for a chunk
        dispatch; out-of-range row indices yield all ``-1`` (drop) rows."""
        out = np.full((len(slots), self.max_blocks_per_req), -1, np.int32)
        for i, s in enumerate(slots):
            if 0 <= s < self.num_slots:
                out[i] = self._tables[s]
        return out

    def tables_device(self):
        """The full (num_slots, max_blocks_per_req) table as a device
        array — the decode dispatch's indirection input. Cached: tables
        mutate only at alloc/free/reset, so the common decode micro-step
        (no admission, no finish) pays no host→device transfer."""
        if self._tables_dev is None:
            self._tables_dev = jnp.asarray(self._tables)
        return self._tables_dev

    @property
    def buffers(self):
        """The pooled cache pytree (k/v: (L, P, bs, Gs, hd))."""
        return self._buf

    def swap_buffers(self, new_buf) -> None:
        """Install the donated-output pool after a dispatch."""
        self._buf = new_buf

    # -- accounting --------------------------------------------------------
    @property
    def capacity_tokens(self) -> int:
        return self.pool.num_blocks * self.block_size

    @property
    def resident_capacity_tokens(self) -> int:
        """Token capacity currently leased (the HBM actually pinned by
        live requests, in token units)."""
        return int(self._nblocks.sum()) * self.block_size

    @property
    def kv_bytes(self) -> int:
        return int(sum(x.nbytes
                       for x in jax.tree_util.tree_leaves(self._buf)))

    def reset(self, *, strict: bool = False) -> None:
        """Return every row and block to the free pools. Rows still
        occupied are lease leaks and are named (warn, or raise under
        ``strict=True``); the block pool runs the same check."""
        leaked = [(s, self._owner[s]) for s in range(self.num_slots)
                  if self._owner[s] is not None]
        if leaked:
            msg = (f"reset with {len(leaked)} live request row(s): "
                   + ", ".join(f"row {s} (owner {o!r})" for s, o in leaked))
            if strict:
                raise LeaseLeakError(msg)
            warnings.warn(msg, LeaseLeakWarning, stacklevel=2)
        if leaked:
            # the row check already named this reset's leak; the pool's
            # own check would re-name the same leases block-by-block
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", LeaseLeakWarning)
                self.pool.reset()
        else:
            # rows clean, but prefix-shared refs can outlive their rows
            self.pool.reset(strict=strict)
        self._tables[:] = -1
        self._tables_dev = None
        self._free_rows = list(range(self.num_slots - 1, -1, -1))
        self._owner = [None] * self.num_slots
        self._nblocks[:] = 0
        self._len[:] = 0

    def reset_rows(self, *, strict: bool = False) -> None:
        """Free every request *row* (and its block lease) while leaving
        the rest of the pool — the prefix cache's parked index and the
        device buffers — intact. This is the warm-cache reset: a new
        trace starts with empty rows but a populated cache. Occupied
        rows are still leaks and are named exactly like :meth:`reset`;
        they are then freed through the ordinary path, so shared blocks
        fall back to the cache (parked) rather than vanishing."""
        leaked = [(s, self._owner[s]) for s in range(self.num_slots)
                  if self._owner[s] is not None]
        if leaked:
            msg = (f"reset with {len(leaked)} live request row(s): "
                   + ", ".join(f"row {s} (owner {o!r})" for s, o in leaked))
            if strict:
                raise LeaseLeakError(msg)
            warnings.warn(msg, LeaseLeakWarning, stacklevel=2)
            for s, _ in leaked:
                self.free(s)
        self._tables_dev = None
