"""Serving engines: static batch (parity baseline) and continuous batching
over the threadcomm substrate (DESIGN.md §8).

``StaticEngine`` is the original fixed-batch path: prefill a whole batch,
decode every row in lockstep until all are done. It stays as the parity
and throughput baseline.

``ContinuousEngine`` interleaves prefill and decode *micro-steps* over a
fixed pool of KV slots (:mod:`repro.serve.kv_cache`) — or, with
``kv_layout="paged"``, over a global pool of fixed-size KV *blocks*
leased through per-request block tables
(:mod:`repro.serve.block_pool`, DESIGN.md §9): admission then gates on
free blocks instead of free slots, prompts deposit chunk-by-chunk
through the tables, and decode is the model's batched block-table step
(`decode_step_paged`), whose attention on a TPU reads each row's live
pages in place through ``kernels/paged_attention`` (the
``serve.decode.dispatch`` span carries ``kv_pages``, ``table_pages``
and ``attn_path``).
Each host step admits requests from the cell-queue scheduler
(:mod:`repro.serve.scheduler`), deposits their prompts, then advances
every live slot by one token. Decode over the pool is a single jit'd
``vmap`` of the model's ``decode_step`` with *per-slot* positions and
donated buffers — each slot's state is fully independent (no shared
mutable state across in-flight requests), which is the serving-side
reading of the MPI+Threads lesson that accidental serialization, not
concurrency itself, is what kills throughput.

Prompt deposit follows the paper's rendezvous discipline, chunked
(DESIGN.md §8): with ``prefill_chunk > 0`` (and a model exposing
``prefill_chunk``) prompts stream into their slot in fixed-size chunks —
up to ``max_prefill_per_step`` chunk-rows from *different* requests are
batched into one fused dispatch per micro-step, interleaved with decode.
A long prompt therefore never monopolizes the device between two decode
steps (no prefill head-of-line blocking), and because the chunk jit's
shapes never change, prefill compiles O(1) XLA programs however many
distinct prompt lengths the traffic carries — versus one compile per
distinct length on the monolithic path (``prefill_chunk=0``), which
stays available as an explicit baseline. Every registry family runs the
chunked path (DESIGN.md §13): SSM/hybrid thread recurrent carried state
through the chunk steps, MoE routes per-token (dropless), and enc-dec
runs its encoder as a fixed pre-chunk on the paged layout. The engine
consults the model's structural capability flags
(``registry.derive_capabilities``) and *raises* naming the missing
capability when a path is unsupported (patch_stub frontends; enc-dec on
the slot layout) — never a silent monolithic fallback.

Threadcomm integration:

* ``comm=`` binds the engine to a (sub-)communicator; prefill inserts and
  decode steps are then threaded through two distinct ``CommStream``s
  ("prefill" / "decode"), giving each domain explicit program order while
  leaving the two free to overlap — the MPIX-stream discipline applied to
  serving.
* Data-parallel replica fan-out is ``Comm.split`` + ``shard_trace``: each
  replica family runs its own engine over its slice of the traffic (see
  ``tests/mp_cases.py::case_serve_replica_fanout``).
* The multi-rank serving fabric (:mod:`repro.serve.fabric`, DESIGN.md
  §10) composes engines across ranks: ``role="prefill"`` engines lease
  prompt-only paged blocks and park finished prefills in
  ``ready_handoffs`` for block-by-block KV migration to a decode rank
  (``begin_import``/``finish_import``), never running a decode dispatch
  themselves.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.compilation_cache import compilation_cache

from repro.core import protocol
from repro.models.transformer import paged_decode_path
# telemetry (DESIGN.md §15): one profiler span per step phase, priced
# hops for the residual ledger, the trial flush
from repro.obs import flush_trial as _obs_flush_trial
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.trace import span
from repro.serve.block_pool import PagedKVCache
from repro.serve.kv_cache import SlotError, SlotKVCache
from repro.serve.prefix_cache import PrefixCache
from repro.serve.scheduler import CellQueueScheduler, ServeRequest


@jax.named_scope("sample")
def _sample_rows(logits, keys, temps):
    """Per-row sampling: greedy when temp <= 0, else temperature
    categorical with that row's own PRNG key. logits (B, Vp)."""
    greedy = jnp.argmax(logits, -1)
    drawn = jax.vmap(
        lambda l, k, t: jax.random.categorical(
            k, l / jnp.maximum(t, 1e-6), -1))(logits, keys, temps)
    return jnp.where(temps > 0.0, drawn, greedy).astype(jnp.int32)


class _NullStream:
    """Stand-in when no communicator is bound: no ordering constraints."""

    def ordered(self, value):
        return value


# ---------------------------------------------------------------------------
# Static batch (the original Engine; parity + throughput baseline)
# ---------------------------------------------------------------------------

class StaticEngine:
    """Fixed-batch engine: one prefill, lockstep decode, done-masking."""

    def __init__(self, model, params, cache_len: int, eos_id: int = -1):
        self.model = model
        self.params = params
        self.cache_len = cache_len
        self.eos_id = eos_id
        self._prefill = jax.jit(
            lambda p, b: model.prefill(p, b, cache_len))
        self._step = jax.jit(model.decode_step, donate_argnums=(1,))

    def generate(self, batch, max_new_tokens: int, *,
                 temperature=0.0, seed: int = 0) -> np.ndarray:
        """batch: model input dict (prompt). Returns (B, max_new) tokens.
        Rows finished early emit ``eos_id``; an all-done batch exits the
        loop (and the remaining columns are already eos-padded).

        ``temperature`` is a scalar or a per-row (B,) vector — a mixed
        batch samples each row at its own temperature (per-row split
        keys) instead of silently applying one row's temperature to all.
        """
        logits, cache = self._prefill(self.params, batch)
        B = logits.shape[0]
        prompt_len = batch["tokens"].shape[1]
        if self.model.cfg.frontend == "patch_stub":
            prompt_len += self.model.cfg.num_frontend_tokens
        temps = np.asarray(temperature, np.float32)
        if temps.ndim == 0:
            temps = np.full((B,), float(temps), np.float32)
        elif temps.shape != (B,):
            raise ValueError(f"temperature must be scalar or ({B},), got "
                             f"shape {temps.shape}")
        key = jax.random.PRNGKey(seed)
        fill = self.eos_id if self.eos_id >= 0 else 0
        out = np.full((B, max_new_tokens), fill, np.int32)
        done = np.zeros((B,), bool)
        tok = self._sample(logits, temps, key)
        for t in range(max_new_tokens):
            out[:, t] = np.where(done, self.eos_id, np.asarray(tok)[:, 0])
            if self.eos_id >= 0:
                done |= out[:, t] == self.eos_id
                if done.all():
                    break
            pos = jnp.int32(prompt_len + t)
            logits, cache = self._step(self.params, cache, tok, pos)
            key, sub = jax.random.split(key)
            tok = self._sample(logits, temps, sub)
        return out

    def _sample(self, logits, temps: np.ndarray, key):
        if (temps <= 0.0).all():
            return jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        keys = jax.random.split(key, logits.shape[0])
        return _sample_rows(logits, keys, jnp.asarray(temps))[:, None]


Engine = StaticEngine   # backwards-compatible alias


# ---------------------------------------------------------------------------
# Continuous batching over the slot pool
# ---------------------------------------------------------------------------

#: parked per-slot decode position: so far below zero that the drop-mode
#: cache writes in ``decode_step`` discard everything a free or
#: still-prefilling slot's vmap row produces
PARK_POS = -(2 ** 30)


@dataclass(eq=False)      # identity equality: deque.remove must never
class _PrefillJob:        # field-compare requests (ndarray __eq__ raises)
    """A partially-deposited prompt: the engine streams ``tokens`` into
    ``slot`` chunk by chunk (``off`` tokens landed so far)."""
    req: ServeRequest
    slot: int
    tokens: np.ndarray            # (prompt_len,) int32
    key: jax.Array                # per-request PRNG key (fold_in(rid))
    off: int = 0


@dataclass(eq=False)
class KVHandoff:
    """A prefill-complete request ready to migrate to a decode rank
    (disaggregated fabric, DESIGN.md §10): the local request row still
    holds the prompt's KV blocks and the sampled-first-token decode
    state. The owning engine keeps the lease until
    :meth:`ContinuousEngine.release_handoff` — the source blocks must
    not be recycled while the transport is still copying out of them."""
    req: ServeRequest
    slot: int                     # source request row
    out: np.ndarray               # (max_new,) output buffer, out[0] = tok0
    length: int                   # resident prompt tokens
    blocks: List[int]             # source pool block ids, table order


class ContinuousEngine:
    """Continuous-batching engine: slot-pool decode + cell-queue admission
    + chunked, batched prefill.

    ``step(now)`` is one micro-step; drive it from a traffic loop (see
    ``repro.launch.serve``) or use :meth:`generate` for the batch-API
    convenience path (same-arrival batch, used by the parity tests).

    ``device`` pins the engine to one device: params, pools and decode
    state are committed there, and stay committed across :meth:`reset`,
    so every dispatch sees the same placement and compiles once (a mix
    of fresh uncommitted buffers and committed dispatch outputs would
    compile each program again per mix). ``None`` leaves placement to
    JAX's default device.
    """

    def __init__(self, model, params, *, cache_len: int, num_slots: int,
                 eos_id: int = -1, scheduler: Optional[CellQueueScheduler] = None,
                 comm=None, max_prefill_per_step: int = 1,
                 prefill_chunk: int = 64, kv_layout: str = "slot",
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 role: str = "full", prefix_cache: bool = False,
                 speculate: int = 0, draft_model=None, draft_params=None,
                 device=None):
        if kv_layout not in ("slot", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r} "
                             "(expected 'slot' or 'paged')")
        if role not in ("full", "prefill", "decode"):
            raise ValueError(f"unknown role {role!r} "
                             "(expected 'full', 'prefill' or 'decode')")
        if role == "prefill" and kv_layout != "paged":
            raise ValueError("a prefill-rank engine hands its KV off "
                             "block-by-block; it requires kv_layout='paged'")
        #: fabric role (DESIGN.md §10): a ``"prefill"`` engine leases
        #: blocks for the prompt only, never decodes, and parks every
        #: prefill-complete request in :attr:`ready_handoffs` for the
        #: transport to migrate; a ``"decode"`` engine receives requests
        #: through :meth:`begin_import`/:meth:`finish_import` instead of
        #: prefilling them. ``"full"`` is the single-engine behavior.
        self.role = role
        self.model = model
        self.device = device
        self.params = self._on_device(params)
        self.cache_len = cache_len
        self.eos_id = eos_id
        self.max_prefill_per_step = max(1, int(max_prefill_per_step))
        self.kv_layout = kv_layout
        #: structural serving capabilities (registry.derive_capabilities);
        #: None for bare stub models, which are treated as fully capable
        self.capabilities = caps = getattr(model, "capabilities", None)
        # chunked prompt deposit: every registry family chunks (state
        # threading for SSM/hybrid, dropless MoE routing, enc-dec via the
        # paged decoder path — DESIGN.md §13). A family that STILL can't
        # (patch_stub frontend; enc-dec on the slot layout) raises here,
        # naming the missing capability — never a silent monolithic
        # fallback (pass prefill_chunk=0 to choose monolithic explicitly).
        chunk = int(prefill_chunk) if prefill_chunk else 0
        if chunk:
            has_chunk = (getattr(model, "prefill_chunk_paged", None)
                         if kv_layout == "paged"
                         else getattr(model, "prefill_chunk", None))
            if has_chunk is None:
                missing = ("chunked_prefill"
                           if caps is None or not caps.chunked_prefill
                           else "slot_chunk")
                hint = (" — this family chunks on the paged path only; "
                        "use kv_layout='paged'"
                        if caps is not None and caps.chunked_prefill
                        and kv_layout == "slot" else "")
                why = (f" ({caps.reason})"
                       if caps is not None and caps.reason else "")
                raise ValueError(
                    f"model lacks capability {missing!r} for chunked "
                    f"prefill on the {kv_layout} layout{hint}{why}; pass "
                    "prefill_chunk=0 for explicit monolithic prefill")
            chunk = min(chunk, int(cache_len))
            mult = int(caps.chunk_multiple) if caps is not None else 1
            if mult > 1:
                # recurrent families resume bit-exactly only when chunk
                # boundaries fall on ssm_chunk multiples: clamp down
                chunk = (chunk // mult) * mult
                if chunk == 0:
                    raise ValueError(
                        f"prefill_chunk={prefill_chunk} (after the "
                        f"cache_len={cache_len} clamp) is below this "
                        f"family's chunk_multiple={mult}; chunk boundaries "
                        f"must fall on multiples of {mult} for bit-exact "
                        "recurrent-state resume")
        self.prefill_chunk = chunk
        if kv_layout == "paged":
            if getattr(model, "decode_step_paged", None) is None:
                why = (f": {caps.reason}"
                       if caps is not None and caps.reason else "")
                raise ValueError(
                    "model lacks capability 'paged_decode' — no "
                    f"block-table paged decode path{why}")
            if not self.prefill_chunk:
                raise ValueError("paged KV deposits prompts chunk-by-chunk;"
                                 " prefill_chunk must be > 0")
            # equal-HBM default: the same token capacity the slot pool
            # would reserve, repartitioned into leased blocks
            mbr = -(-int(cache_len) // int(block_size))
            nblocks = (int(num_blocks) if num_blocks
                       else -(-num_slots * int(cache_len) // int(block_size)))
            self.kv = PagedKVCache(model, num_blocks=nblocks,
                                   block_size=int(block_size),
                                   num_slots=num_slots,
                                   max_blocks_per_req=mbr)
        else:
            self.kv = SlotKVCache(model, cache_len, num_slots)
        self.kv.swap_buffers(self._on_device(self.kv.buffers))
        #: how paged decode attention reads the pool ("paged_kernel" or
        #: "gather", transformer.paged_decode_path); None without one
        self.attn_path = None
        if kv_layout == "paged" and "k" in self.kv.buffers:
            k = self.kv.buffers["k"]
            self.attn_path = paged_decode_path(
                k.shape[3], k.shape[4], next(iter(k.devices())).platform)
        if prefix_cache:
            # radix-tree prefix cache (DESIGN.md §12): admission walks
            # the trie, leases every hit block at refcount+1, and starts
            # chunked prefill at the first miss offset; the cache is the
            # pool's attached reclaimer (LRU eviction of parked blocks)
            if kv_layout != "paged":
                raise ValueError("prefix caching shares paged KV blocks; "
                                 "it requires kv_layout='paged'")
            if role != "full":
                raise ValueError("prefix caching is not supported on "
                                 "disaggregated prefill/decode ranks "
                                 "(migrated blocks leave the local pool)")
            if caps is not None and not caps.prefix_cache:
                raise ValueError("model lacks capability 'prefix_cache': "
                                 + caps.reason)
            if getattr(model, "clone_paged_block", None) is None:
                raise ValueError("prefix caching needs the model's "
                                 "copy-on-write block clone "
                                 "(clone_paged_block)")
            self.prefix_cache = PrefixCache(self.kv.pool)
            self._cow_clone = jax.jit(model.clone_paged_block,
                                      donate_argnums=(0,))
        else:
            self.prefix_cache = None
        # speculative decoding (DESIGN.md §14): a drafter proposes k
        # tokens per round on its OWN paged pool; the target verifies
        # them in one fused (k+1)-query dispatch and rejected draft KV
        # rows roll back structurally (length decrement + the next
        # dispatch's drop-mode overwrite — no blanking)
        self.speculate = int(speculate)
        if self.speculate < 0:
            raise ValueError(f"speculate must be >= 0, got {speculate}")
        if self.speculate:
            if kv_layout != "paged":
                raise ValueError("speculative decoding rolls rejected "
                                 "draft KV back through block tables; it "
                                 "requires kv_layout='paged'")
            if role != "full":
                raise ValueError("speculative decoding needs draft and "
                                 "verify on one engine; it is not "
                                 "supported on disaggregated "
                                 "prefill/decode ranks")
            if self.prefix_cache is not None:
                raise ValueError(
                    "speculative decoding does not compose with prefix "
                    "caching: rolled-back draft rows would sit inside "
                    "blocks the radix cache could lease to another "
                    "request as canonical prefix KV")
            if caps is not None and not caps.speculative:
                raise ValueError("model lacks capability 'speculative': "
                                 + caps.reason)
            if getattr(model, "verify_step_paged", None) is None:
                raise ValueError("speculative decoding needs the model's "
                                 "k-token teacher-forced verify dispatch "
                                 "(verify_step_paged)")
            if draft_model is None:
                # self-speculation: the target drafts for itself on a
                # second pool — the degenerate pairing that exercises
                # the full draft-verify-rollback machinery with a
                # near-1.0 acceptance rate (smoke/CI default)
                draft_model, draft_params = model, params
            else:
                if draft_params is None:
                    raise ValueError("draft_model needs draft_params")
                if draft_model.cfg.vocab_size != model.cfg.vocab_size:
                    raise ValueError(
                        f"drafter vocab {draft_model.cfg.vocab_size} != "
                        f"target vocab {model.cfg.vocab_size}: drafted "
                        "token ids would not index the target's "
                        "distribution")
                dcaps = getattr(draft_model, "capabilities", None)
                if dcaps is not None and not dcaps.speculative:
                    raise ValueError("draft model lacks capability "
                                     "'speculative': " + dcaps.reason)
                if getattr(draft_model, "verify_step_paged", None) is None:
                    raise ValueError(
                        "the drafter resyncs through its own teacher-"
                        "forced verify dispatch (verify_step_paged)")
            self.draft_model = draft_model
            self.draft_params = self._on_device(draft_params)
            # the drafter's pool mirrors the target's geometry so rows
            # and leases stay 1:1 (alloc/free in lockstep); its HBM cost
            # is the drafter's own (smaller) per-token KV
            self.draft_kv = PagedKVCache(
                draft_model, num_blocks=self.kv.pool.num_blocks,
                block_size=self.kv.block_size, num_slots=num_slots,
                max_blocks_per_req=self.kv.max_blocks_per_req)
            self.draft_kv.swap_buffers(self._on_device(self.draft_kv.buffers))
            #: drafter's canonical resident tokens per row (host-side;
            #: the drafter pool's own length bookkeeping is unused — the
            #: model path takes explicit positions)
            self._draft_len = np.zeros((num_slots,), np.int32)
        self.scheduler = scheduler or CellQueueScheduler(
            num_cells=4 * num_slots,
            prefill_chunk_bytes=4 * self.prefill_chunk,
            block_bytes=(4 * int(block_size)
                         if kv_layout == "paged" else 0),
            state_bytes=self._carried_state_bytes())
        if comm is not None:
            self._prefill_stream = comm.stream("prefill")
            self._decode_stream = comm.stream("decode")
            # draft and verify are distinct execution domains (the
            # drafter's pool advances independently of the target's):
            # each gets its own program order, free to overlap the other
            self._draft_stream = comm.stream("draft")
            self._verify_stream = comm.stream("verify")
        else:
            self._prefill_stream = _NullStream()
            self._decode_stream = _NullStream()
            self._draft_stream = _NullStream()
            self._verify_stream = _NullStream()

        # trace counters ~= XLA compile counts (a jit retraces exactly
        # when it compiles a new program); the bench artifact uses these
        # to show chunked prefill compiles O(1) programs while monolithic
        # prefill compiles one per distinct prompt length
        self.prefill_compiles = 0
        self.decode_compiles = 0

        def _prefill_traced(p, b):
            self.prefill_compiles += 1
            return model.prefill(p, b, cache_len)

        decode_fn = (self._decode_impl_paged(model)
                     if kv_layout == "paged" else self._decode_impl(model))

        def _decode_traced(p, buf, state, *rest):
            self.decode_compiles += 1
            return decode_fn(p, buf, state, *rest)

        self._prefill = jax.jit(_prefill_traced)
        self._decode = jax.jit(_decode_traced, donate_argnums=(1, 2))
        # enc-dec: the encoder pass as a fixed pre-chunk at admission —
        # installs the request's per-layer cross K/V carried state into
        # its cache row before the decoder chunk stream starts
        enc = getattr(model, "encode_prechunk", None)
        self._encode = (jax.jit(enc, donate_argnums=(1,))
                        if enc is not None and kv_layout == "paged"
                        else None)
        self._admit_state = jax.jit(self._admit_impl, donate_argnums=(0,))
        self._park_state = jax.jit(self._park_impl, donate_argnums=(0,))
        self._import_state = jax.jit(self._import_state_impl,
                                     donate_argnums=(0,))
        if self.prefill_chunk:
            chunk_fn = (self._chunk_impl_paged(model, num_slots)
                        if kv_layout == "paged"
                        else self._chunk_impl(model, num_slots))

            def _chunk_traced(p, buf, state, *rest):
                self.prefill_compiles += 1
                return chunk_fn(p, buf, state, *rest)

            self._chunk = jax.jit(_chunk_traced, donate_argnums=(1, 2))
        if self.speculate:
            spec_fn = self._spec_round_impl(model, self.draft_model,
                                            self.speculate)

            def _spec_traced(p, dp, buf, dbuf, *rest):
                self.decode_compiles += 1
                return spec_fn(p, dp, buf, dbuf, *rest)

            self._spec_round = jax.jit(_spec_traced, donate_argnums=(2, 3))

            def _draft_chunk_fn(dp, dbuf, tokens, tables, rows, pos0,
                                n_valid):
                # mirror of the target's prompt deposit into the
                # drafter's pool: logits are discarded (the drafter's
                # first proposal comes from the resync dispatch)
                _, dbuf = self.draft_model.prefill_chunk_paged(
                    dp, dbuf, tokens, tables, rows, pos0, n_valid)
                return dbuf

            self._draft_chunk = jax.jit(_draft_chunk_fn,
                                        donate_argnums=(1,))
        #: partially-deposited requests, FIFO; each micro-step serves the
        #: first ``max_prefill_per_step`` of them with one fused dispatch
        self._prefilling: Deque[_PrefillJob] = deque()
        #: wall seconds of each ``serve.*`` phase of the latest step,
        #: kept whether or not a profiler runs
        self.last_step_phases: Dict[str, float] = {}
        #: role="prefill": prefill-complete requests awaiting migration
        #: (their rows/blocks stay leased until release_handoff)
        self.ready_handoffs: List[KVHandoff] = []

        # per-slot sampling/position state lives ON DEVICE and is updated
        # inside the jits (donated) — the decode hot loop costs one
        # dispatch + one small token sync per micro-step, no host↔device
        # state shuttling. Positions start PARKED (far negative): rows of
        # slots that are free or mid-prefill write nothing (drop-mode
        # scatter in decode_step) however often the pool vmap advances.
        S = num_slots
        self._state = self._fresh_state(S)
        self._slot_req: List[Optional[ServeRequest]] = [None] * S
        self._slot_out: List[Optional[np.ndarray]] = [None] * S

        # serving accounting: peak in-flight requests plus resident-vs-
        # reserved token sums — the slot-vs-paged HBM-efficiency evidence
        # the traffic driver reports (bytes pinned per resident token)
        self.peak_live = 0
        self._resident_tok_sum = 0
        self._reserved_tok_sum = 0

        # prefix-cache accounting (stays zero when the cache is off):
        # hit tokens never re-prefill, so saved tokens == hit tokens and
        # saved dispatches is the per-request chunk-count difference
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0
        self.prefix_prompt_tokens = 0
        self.prefill_dispatches_saved = 0
        self.prefix_cow_clones = 0

    def _carried_state_bytes(self) -> int:
        """Per-request bytes of carried (non-KV) state — the scheduler
        prices this one extra interthread handoff per admission (the
        state row travels with the request, unlike pool-resident KV)."""
        caps = self.capabilities
        if caps is None or not caps.carried_state:
            return 0
        buf = self.kv.buffers
        total = sum(int(buf[name].nbytes) for name in caps.state_leaves
                    if isinstance(buf, dict) and name in buf)
        return total // max(1, self.kv.num_slots)

    def _on_device(self, tree):
        """``tree`` committed to the engine's device (as is, without one)."""
        return tree if self.device is None else jax.device_put(tree,
                                                               self.device)

    def _fresh_state(self, S: int):
        return self._on_device({
            "tok": jnp.zeros((S, 1, 1), jnp.int32),    # next input token
            "pos": jnp.full((S,), PARK_POS, jnp.int32),  # next decode pos
            "keys": jnp.zeros((S, 2), jnp.uint32),     # per-slot PRNG keys
            "temp": jnp.zeros((S,), jnp.float32),
        })

    @staticmethod
    @jax.named_scope("sample")
    def _advance_state(state, logits):
        """Shared decode tail of the slot and paged dispatches: sample
        each row with its own key chain and advance (tok, pos, keys).
        MUST stay one copy — a sampling fix applied to one layout only
        would silently diverge their token streams and break the
        slot-vs-paged parity CI asserts. logits (S, Vp)."""
        split = jax.vmap(jax.random.split)(state["keys"])      # (S, 2, 2)
        nxt = _sample_rows(logits, split[:, 1], state["temp"])
        return nxt, {"tok": nxt.reshape(-1, 1, 1),
                     "pos": state["pos"] + 1,
                     "keys": split[:, 0],
                     "temp": state["temp"]}

    @classmethod
    def _decode_impl(cls, model):
        vstep = jax.vmap(model.decode_step, in_axes=(None, 0, 0, 0))

        def fn(params, buf, state):
            logits, buf = vstep(params, buf, state["tok"],
                                state["pos"])            # logits (S, 1, Vp)
            nxt, state = cls._advance_state(state, logits[:, 0, :])
            return nxt, buf, state

        return fn

    @staticmethod
    def _admit_impl(state, logits, slot, key, temp, pos0):
        """Seed slot ``slot`` from the prefill logits: sample the first
        token with the request's own key, install (tok, pos, key, temp)."""
        key, sub = jax.random.split(key)
        tok0 = _sample_rows(logits, sub[None], temp[None])[0]
        # scatter-drop: slot-indexed writes carry explicit drop semantics
        # like every other slot scatter, so a bad index writes nothing
        # instead of clamping onto a live row
        state = {
            "tok": state["tok"].at[slot].set(tok0, mode="drop"),
            "pos": state["pos"].at[slot].set(pos0, mode="drop"),
            "keys": state["keys"].at[slot].set(key, mode="drop"),
            "temp": state["temp"].at[slot].set(temp, mode="drop"),
        }
        return state, tok0

    @staticmethod
    def _park_impl(state, slot):
        """Park a retired slot's position: its decode-vmap row keeps
        computing, but the drop-mode cache writes discard everything."""
        # scatter-drop: same drop discipline as the cache writes
        return {**state,
                "pos": state["pos"].at[slot].set(PARK_POS, mode="drop")}

    @staticmethod
    def _import_state_impl(state, slot, tok, pos, key, temp):
        """Install a *migrated* request's decode state at ``slot`` — the
        exact (tok, pos, key, temp) the source rank's finalize produced,
        no resampling (the first token was already drawn there; replaying
        the draw here would fork the request's PRNG chain)."""
        # scatter-drop: slot-indexed writes carry explicit drop semantics
        return {
            "tok": state["tok"].at[slot].set(tok, mode="drop"),
            "pos": state["pos"].at[slot].set(pos, mode="drop"),
            "keys": state["keys"].at[slot].set(key, mode="drop"),
            "temp": state["temp"].at[slot].set(temp, mode="drop"),
        }

    @staticmethod
    @jax.named_scope("sample")
    def _install_finalized_rows(state, logits, rows, fin_pos, keys, temps,
                                drop_row):
        """Shared chunked-prefill tail of the slot and paged dispatches:
        sample the first token of every chunk-row and install the decode
        state of rows whose prompt just completed (``fin_pos >= 0``) —
        exactly as monolithic admission would; non-final and padding rows
        aim at ``drop_row`` and write nothing. One copy for both layouts,
        for the same parity reason as :meth:`_advance_state`."""
        split = jax.vmap(jax.random.split)(keys)          # (P, 2, 2)
        tok0 = _sample_rows(logits, split[:, 1], temps)   # (P,)
        fin = fin_pos >= 0
        trow = jnp.where(fin, rows, drop_row)             # drop non-final
        state = {
            "tok": state["tok"].at[trow].set(
                tok0.reshape(-1, 1, 1), mode="drop"),
            "pos": state["pos"].at[trow].set(fin_pos, mode="drop"),
            "keys": state["keys"].at[trow].set(split[:, 0], mode="drop"),
            "temp": state["temp"].at[trow].set(temps, mode="drop"),
        }
        return state, tok0

    @classmethod
    def _chunk_impl(cls, model, num_slots):
        """One fused chunked-prefill dispatch over up to P chunk-rows from
        different requests: gather their slot rows, run the model's
        fixed-shape ``prefill_chunk`` vmapped across rows, scatter the
        rows back, then the shared finalize tail. Padding rows carry
        ``slots == num_slots``: the gather clamps and every write
        drops."""
        vchunk = jax.vmap(model.prefill_chunk, in_axes=(None, 0, 0, 0, 0))

        def fn(params, buf, state, tokens, slots, pos0, n_valid, fin_pos,
               keys, temps):
            rows = SlotKVCache.rows_at(buf, slots)
            logits, new_rows = vchunk(params, rows, tokens, pos0, n_valid)
            buf = SlotKVCache.rows_into(buf, new_rows, slots)
            state, tok0 = cls._install_finalized_rows(
                state, logits, slots, fin_pos, keys, temps, num_slots)
            return buf, state, tok0

        return fn

    @classmethod
    def _decode_impl_paged(cls, model):
        """One decode micro-step over the paged pool: the model's batched
        block-table decode (no outer vmap — the pool is one shared
        buffer), then the same in-jit sampling tail as the slot path."""
        def fn(params, buf, state, tables):
            logits, buf = model.decode_step_paged(
                params, buf, state["tok"][:, 0], state["pos"],
                tables)                                # logits (S, Vp)
            nxt, state = cls._advance_state(state, logits)
            return nxt, buf, state

        return fn

    @classmethod
    def _chunk_impl_paged(cls, model, num_slots):
        """One fused chunked-prefill dispatch through block tables: up to
        P chunk-rows write straight into the shared pool (the table IS
        the indirection — no slot-row gather/scatter), then the shared
        finalize tail. Padding rows carry an all ``-1`` table (writes
        drop) and ``rows == num_slots`` (state installs drop — for both
        the sampling state and the model's carried recurrent state, which
        the chunk step gathers/scatters at the same row indices)."""
        def fn(params, buf, state, tokens, rows, tables, pos0, n_valid,
               fin_pos, keys, temps):
            logits, buf = model.prefill_chunk_paged(
                params, buf, tokens, tables, rows, pos0, n_valid)
            state, tok0 = cls._install_finalized_rows(
                state, logits, rows, fin_pos, keys, temps, num_slots)
            return buf, state, tok0

        return fn

    @staticmethod
    def _spec_round_impl(model, draft_model, k):
        """One fused draft–verify round (DESIGN.md §14), everything on
        device — drafter resync, k-token autoregressive draft, the
        target's single (k+1)-query verify, and longest-matching-prefix
        acceptance — so the host pays ONE token sync per round (the
        spec-mode analogue of ``_decode_micro_step``'s one sync).

        Per live row: the drafter first *resyncs* — a fixed width-2
        teacher-forced dispatch consuming the ``u ∈ {1, 2}`` canonical
        tokens it has not seen (``u == 2`` exactly after a fully-accepted
        round; the emitted-token history lives on the host, so ``prev``/
        ``cur`` arrive as inputs) — whose last valid logits row yields
        draft 1; then ``k - 1`` single-token drafter decode steps extend
        the proposal. The target verifies ``[cur, d_1 .. d_k]`` in one
        fused dispatch; ``greedy[:, j]`` is its next-token choice after
        consuming tokens through ``j``, so the longest matching prefix
        plus the target's own token at the first mismatch reproduces
        sequential greedy decode token-for-token. Returned ``greedy`` is
        the emission buffer itself: tokens ``greedy[b, :n_emit[b]]`` are
        exactly what sequential decode would have produced.

        Rollback is structural: rejected draft rows (target pool) and
        unaccepted drafter rows sit at positions beyond the new canonical
        length — out of causal range (``kpos <= qpos``) for every later
        valid query until a later dispatch's drop-mode write overwrites
        them. Drafter steps beyond ``n_draft`` park their write position
        (``PARK_POS``): near the request's token budget the clamp
        ``n_draft < k`` would otherwise let a stale draft write overrun
        the row's block lease."""
        def fn(params, dparams, buf, dbuf, cur, prev, u, sync_pos, tpos,
               n_draft, tables, dtables):
            sync_tok = jnp.where((u == 2)[:, None],
                                 jnp.stack([prev, cur], axis=1),
                                 jnp.stack([cur, cur], axis=1))
            dlogits, dbuf = draft_model.verify_step_paged(
                dparams, dbuf, sync_tok, sync_pos, dtables, u)
            dnext = jnp.argmax(dlogits, -1).astype(jnp.int32)
            drafts = [jnp.take_along_axis(
                dnext, jnp.maximum(u - 1, 0)[:, None], axis=1)[:, 0]]
            base = sync_pos + u            # next drafter write position
            for j in range(k - 1):
                pos_j = jnp.where(j + 1 <= n_draft, base + j, PARK_POS)
                lg, dbuf = draft_model.decode_step_paged(
                    dparams, dbuf, drafts[-1][:, None], pos_j, dtables)
                drafts.append(jnp.argmax(lg, -1).astype(jnp.int32))
            drafts = jnp.stack(drafts, axis=1)                    # (S, k)
            vtok = jnp.concatenate([cur[:, None], drafts], axis=1)
            logits, buf = model.verify_step_paged(
                params, buf, vtok, tpos, tables, n_draft + 1)
            greedy = jnp.argmax(logits, -1).astype(jnp.int32)   # (S, k+1)
            match = ((drafts == greedy[:, :k])
                     & (jnp.arange(k)[None, :] < n_draft[:, None]))
            n_acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), 1), 1)
            return greedy, n_acc + 1, buf, dbuf

        return fn

    # -- request intake ----------------------------------------------------
    def submit(self, req: ServeRequest, now: float = 0.0) -> str:
        """Queue a request through the cell-queue scheduler. A paged
        request whose token budget can never fit its block-table is
        rejected here, at submit — not discovered as a crash in the
        admission gate once it reaches the queue head."""
        if self.speculate and req.temperature > 0.0:
            raise ValueError(
                f"request {req.rid}: speculative decoding verifies "
                "greedy token identity (longest-matching-prefix "
                "acceptance is exact for argmax only); temperature must "
                f"be 0, got {req.temperature}")
        if self.kv_layout == "paged":
            budget = self._token_budget(req)
            cap = self.admittable_tokens
            if budget > cap:
                # a prefill-rank lease is prompt-only; the message must
                # name the quantity actually rejected
                what = ("prompt" if self.role == "prefill"
                        else "prompt+max_new")
                fix = ("" if self.role == "prefill"
                       else " or lower max_new_tokens")
                raise ValueError(
                    f"request {req.rid}: {what} = {budget} tokens "
                    f"exceeds the admittable capacity {cap} (= min(table "
                    f"cap {self.kv.max_blocks_per_req}, pool "
                    f"{self.kv.pool.num_blocks}) blocks x "
                    f"{self.kv.block_size}); raise cache_len/num_blocks"
                    f"{fix}")
        return self.scheduler.submit(req, now)

    @property
    def admittable_tokens(self) -> int:
        """Largest token budget one request could ever lease here: a
        lease must fit BOTH caps, the per-request table and the whole
        pool — a request needing more blocks than exist would otherwise
        be accepted and livelock admission (head-of-line deferral that
        can never clear). Unbounded for the slot layout (ring
        recycling serves arbitrarily long decodes at fixed footprint)."""
        if self.kv_layout != "paged":
            return 2 ** 31 - 1
        return (min(self.kv.max_blocks_per_req, self.kv.pool.num_blocks)
                * self.kv.block_size)

    @property
    def num_active(self) -> int:
        return self.kv.num_live

    @property
    def num_prefilling(self) -> int:
        """Requests admitted to a slot but still streaming their prompt."""
        return len(self._prefilling)

    @property
    def num_decoding(self) -> int:
        return sum(r is not None for r in self._slot_req)

    @property
    def idle(self) -> bool:
        return self.kv.num_live == 0 and self.scheduler.num_waiting == 0

    # -- micro-step --------------------------------------------------------
    def step(self, now: float = 0.0) -> List[ServeRequest]:
        """One serving micro-step: deposit prompt material for up to
        ``max_prefill_per_step`` requests (one chunk-row each, fused into
        a single dispatch on the chunked path), then advance every
        decoding slot by one token. Returns the requests that finished
        this step.

        Each phase runs in a :func:`~repro.obs.trace.span` (``serve.*``)
        whose wall time also lands in :attr:`last_step_phases`."""
        phases: Dict[str, float] = {}
        self.last_step_phases = phases
        # runnable-work hint for the serialization-stall detector: rows +
        # queued requests this engine could be advancing
        obs_trace.set_runnable(self.kv.num_live + self.scheduler.num_waiting)
        finished: List[ServeRequest] = []
        with span("serve.step", phases, rows=self.kv.num_live,
                  prefilling=len(self._prefilling),
                  decoding=self.num_decoding,
                  blocks_free=(self.kv.num_free_blocks
                               if self.kv_layout == "paged"
                               else self.kv.num_free)) as sp:
            deferrals = self.scheduler.n_block_deferrals
            if self.prefill_chunk:
                self._admit_chunked(now, phases)
                if self._prefilling:
                    finished.extend(self._prefill_chunk_step(now, phases))
            else:
                n_admit = min(self.kv.num_free, self.max_prefill_per_step)
                for req in self.scheduler.admit(now, n_admit):
                    with span("serve.prefill", phases, rid=req.rid,
                              prompt_len=req.prompt_len) as adm:
                        done = self._admit(req, now)
                        adm.hop("admission", req.admit_cost_s)
                    if done is not None:
                        finished.append(done)
            if self.num_decoding:
                finished.extend(self._spec_micro_step(now, phases)
                                if self.speculate
                                else self._decode_micro_step(now, phases))
            self._account()
            sp.set(deferred=self.scheduler.n_block_deferrals - deferrals,
                   finished=len(finished))
        return finished

    def _admit_chunked(self, now: float, phases: Dict[str, float]) -> None:
        """Admit queued requests into the prefilling state: at most
        ``max_prefill_per_step`` prompts are in flight, and each gets one
        chunk per micro-step, so decode stalls are bounded by one chunk of
        prefill compute."""
        budget = min(self.kv.num_free,
                     self.max_prefill_per_step - len(self._prefilling))
        # paged: the second admission gate is the block pool — a request
        # is held back (head-of-line) until its whole token budget
        # (prompt + max_new) fits in free blocks. Admit one request at a
        # time so each lease is debited from the free pool before the
        # next candidate is gated. With the prefix cache, only the miss
        # tail needs fresh blocks: the gate prices the hit so
        # shared-prefix bursts admit earlier.
        can = ((lambda r: self.kv.can_admit(
            self._token_budget(r),
            hit=(self._prefix_lookup(r) if self.prefix_cache
                 is not None else None)))
            if self.kv_layout == "paged" else None)
        while budget > 0:
            admitted = self.scheduler.admit(now, 1, can_admit=can)
            if not admitted:
                break
            req = admitted[0]
            with span("serve.admit", phases, rid=req.rid,
                      prompt_len=req.prompt_len, protocol=req.protocol,
                      waited_s=req.admit_time - req.arrival) as sp:
                self._begin_prefill(req)
                # the admission hop's wall-clock twin of the §3.2 price
                # stamped on the request (repriced to the prefix-hit
                # model when the radix cache served it)
                sp.hop("prefix_hit" if req.prefix_hit_tokens > 0
                       else "admission", req.admit_cost_s)
                sp.set(prefix_hit_tokens=req.prefix_hit_tokens)
            budget -= 1

    def _token_budget(self, req: ServeRequest) -> int:
        """Token capacity a request leases at admission: the prompt plus
        every token it may generate (no mid-decode block exhaustion). A
        prefill-rank engine leases the prompt only — the first generated
        token's KV (and every one after it) is written on the decode
        rank that receives the migrated blocks."""
        if self.role == "prefill":
            return req.prompt_len
        return req.prompt_len + req.max_new_tokens

    def _account(self) -> None:
        live = self.kv.num_live
        self.peak_live = max(self.peak_live, live)
        if live:
            self._resident_tok_sum += int(self.kv.lengths.sum())
            self._reserved_tok_sum += (
                self.kv.resident_capacity_tokens
                if self.kv_layout == "paged" else live * self.cache_len)

    def kv_accounting(self) -> dict:
        """Thin alias — the canonical schema lives in
        :func:`repro.obs.metrics.engine_kv_accounting` (DESIGN.md §15),
        so every stats surface is assembled in one place."""
        return obs_metrics.engine_kv_accounting(self)

    def prefix_stats(self) -> dict:
        """Thin alias — canonical schema:
        :func:`repro.obs.metrics.engine_prefix_stats`."""
        return obs_metrics.engine_prefix_stats(self)

    @property
    def decode_tokens_per_dispatch(self) -> float:
        """Tokens one decode dispatch yields on this engine: 1.0 without
        speculation; with it, the observed mean accepted-per-dispatch
        (or the ``(k + 2) / 2`` uniform-acceptance prior before any
        round has run). The fabric's placement cost model divides decode
        dispatch counts by this — a hardcoded one-token-per-dispatch
        assumption would systematically overprice speculative ranks."""
        if not self.speculate:
            return 1.0
        sch = self.scheduler
        if sch.n_spec_dispatches:
            return sch.spec_accepted_tokens / sch.n_spec_dispatches
        return (self.speculate + 2) / 2

    def spec_stats(self) -> dict:
        """Thin alias — canonical schema:
        :func:`repro.obs.metrics.engine_spec_stats`."""
        return obs_metrics.engine_spec_stats(self)

    def program_text(self) -> Dict[str, str]:
        """Optimized HLO of the paged decode and chunk programs at this
        engine's shapes, keyed by jitted name. A profile names each device
        op by its HLO instruction; the instruction's ``op_name`` metadata
        carries the model's named scopes (``attention``, ``mlp``,
        ``lm_head``, ``sample``...).

        Traced and compiled anew, past JAX's caches: the persistent cache
        keys a program without its metadata, so the executable the engine
        runs may carry the op names of a build that differs only in its
        scopes. The instructions' names are the same."""
        if self.kv_layout != "paged" or not self.prefill_chunk:
            raise ValueError("program_text covers the paged chunked engine")
        P, C = self.max_prefill_per_step, self.prefill_chunk
        row = jnp.zeros((P,), jnp.int32)
        programs = {
            "_chunk_traced": (
                self._chunk_impl_paged(self.model, self.kv.num_slots),
                (jnp.zeros((P, C), jnp.int32), row,
                 jnp.zeros((P, self.kv.max_blocks_per_req), jnp.int32), row,
                 row, row, jnp.zeros((P, 2), jnp.uint32),
                 jnp.zeros((P,), jnp.float32))),
            "_decode_traced": (self._decode_impl_paged(self.model),
                               (self.kv.tables_device(),)),
        }
        cached = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            return {name: jax.jit(fn, donate_argnums=(1, 2)).lower(
                self.params, self.kv.buffers, self._state, *rest
            ).compile().as_text() for name, (fn, rest) in programs.items()}
        finally:
            jax.config.update("jax_enable_compilation_cache", cached)
            compilation_cache.reset_cache()

    # -- chunked prompt deposit (rendezvous-style streaming) ---------------
    def _begin_prefill(self, req: ServeRequest) -> None:
        """Claim a slot (or lease blocks + a request row) and enter the
        ``prefilling`` state: the prompt will stream in chunk by chunk
        across micro-steps."""
        resident = 0
        if self.kv_layout == "paged":
            # no blanking needed: paged masking is structural (a stale
            # page of a block's previous owner is never at a position
            # <= qpos of the new owner)
            if self.prefix_cache is not None:
                slot, resident = self._admit_with_prefix(req)
            else:
                slot = self.kv.alloc(req, self._token_budget(req))
            if self.speculate:
                # lockstep lease: the drafter's pool mirrors every
                # alloc/free, so both pools always hand out the same row
                dslot = self.draft_kv.alloc(req, self._token_budget(req))
                if dslot != slot:
                    raise SlotError(
                        f"drafter row {dslot} diverged from target row "
                        f"{slot} for request {req.rid}: the pools' "
                        "alloc/free lockstep broke")
            if self._encode is not None:
                # enc-dec: the fixed encoder pre-chunk — install this
                # request's cross K/V carried state into its row before
                # the decoder prompt starts streaming
                buf = self._encode(self.params, self.kv.buffers,
                                   jnp.asarray(req.batch["frames"]),
                                   jnp.full((1,), slot, jnp.int32))
                self.kv.swap_buffers(self._prefill_stream.ordered(buf))
        else:
            slot = self.kv.alloc(req)
            self.kv.reset_slot(slot)   # stale pages must not alias history
        req.state = "prefilling"
        tokens = np.asarray(req.batch["tokens"][0], np.int32)
        key = jax.random.fold_in(jax.random.PRNGKey(req.seed), req.rid)
        self._prefilling.append(_PrefillJob(req=req, slot=slot,
                                            tokens=tokens, key=key,
                                            off=resident))

    def _prefix_lookup(self, req: ServeRequest):
        """Longest cached prefix of the prompt, clamped one token short
        of the full length: the final chunk always re-prefills, so its
        last-position logits exist to seed decode."""
        tokens = np.asarray(req.batch["tokens"][0], np.int32)
        return self.prefix_cache.lookup(tokens, limit=len(tokens) - 1)

    def _admit_with_prefix(self, req: ServeRequest):
        """Paged admission through the radix cache: lease every hit
        block at refcount+1, allocate fresh blocks for the miss tail
        only, clone the divergent block for a partial (CoW) hit, and
        start chunked prefill at the first miss offset. Returns
        ``(slot, resident)`` — resident tokens never re-prefill."""
        hit = self._prefix_lookup(req)
        slot = self.kv.alloc_prefix(req, self._token_budget(req), hit,
                                    self.prefix_cache)
        resident = hit.tokens
        if hit.cow_src is not None:
            # copy-on-write: duplicate the shared block's pages into the
            # request's first fresh (private) block on device, then drop
            # the temporary source reference — the request resumes its
            # chunked deposit mid-block and overwrites only the
            # divergent tail, never touching the shared source
            dst = self.kv.blocks_of(slot)[len(hit.blocks)]
            buf = self._cow_clone(self.kv.buffers, jnp.int32(hit.cow_src),
                                  jnp.int32(dst))
            self.kv.swap_buffers(self._prefill_stream.ordered(buf))
            self.prefix_cache.release_cow(hit.cow_src)
            resident += hit.cow_tokens
            self.prefix_cow_clones += 1
        if resident:
            self.kv.advance(slot, resident)
        plen = req.prompt_len
        self.prefix_lookups += 1
        self.prefix_prompt_tokens += plen
        if resident:
            C = self.prefill_chunk
            self.prefix_hits += 1
            self.prefix_hit_tokens += resident
            self.prefill_dispatches_saved += (
                -(-plen // C) - -(-(plen - resident) // C))
            req.prefix_hit_tokens = resident
            self.scheduler.reprice_prefix(
                req, resident, cow_blocks=int(hit.cow_src is not None))
        return slot, resident

    def _prefill_chunk_step(self, now: float,
                            phases: Dict[str, float]) -> List[ServeRequest]:
        """One fused dispatch: the next chunk of up to
        ``max_prefill_per_step`` prefilling requests, batched row-wise at
        fixed shapes (shorter tails padded + masked, absent rows aimed at
        the drop slot)."""
        P, C = self.max_prefill_per_step, self.prefill_chunk
        S = self.kv.num_slots
        jobs = list(self._prefilling)[:P]
        with span("serve.chunk.build", phases):
            tok = np.zeros((P, C), np.int32)
            slots = np.full((P,), S, np.int32)         # S = drop row
            pos0 = np.zeros((P,), np.int32)
            n_valid = np.zeros((P,), np.int32)
            fin_pos = np.full((P,), -1, np.int32)
            temps = np.zeros((P,), np.float32)
            keys = np.zeros((P, 2), np.uint32)
            for i, job in enumerate(jobs):
                n = min(C, len(job.tokens) - job.off)
                tok[i, :n] = job.tokens[job.off:job.off + n]
                slots[i] = job.slot
                pos0[i] = job.off
                n_valid[i] = n
                if job.off + n >= len(job.tokens):
                    fin_pos[i] = len(job.tokens)       # next decode position
                temps[i] = job.req.temperature
                keys[i] = np.asarray(job.key, np.uint32)
                job.req.prefill_chunks += 1
            host = [tok, slots, pos0, n_valid, fin_pos, keys, temps]
            if self.kv_layout == "paged":
                # rows double as state-install targets (S = drop row); the
                # block tables are the write indirection — padding rows
                # carry all -1 tables, so every pool write drops
                host.insert(2, self.kv.table_rows(slots))
            args = [jnp.asarray(a) for a in host]
        with span("serve.chunk.dispatch", phases):
            buf, state, tok0 = self._chunk(self.params, self.kv.buffers,
                                           self._state, *args)
            self.kv.swap_buffers(self._prefill_stream.ordered(buf))
            self._state = state
            if self.kv_layout == "paged" and self.speculate:
                # mirror the prompt chunk into the drafter's pool: same
                # tokens/offsets, the drafter's own tables (its rows were
                # leased in lockstep at _begin_prefill)
                tok_d, slots_d, _, pos0_d, n_valid_d = args[:5]
                dbuf = self._draft_chunk(
                    self.draft_params, self.draft_kv.buffers, tok_d,
                    jnp.asarray(self.draft_kv.table_rows(slots)), slots_d,
                    pos0_d, n_valid_d)
                self.draft_kv.swap_buffers(self._draft_stream.ordered(dbuf))

        tok0_np = None
        if (fin_pos >= 0).any():
            with span("serve.chunk.sync", phases):
                # host sync only when a prompt completes
                tok0_np = np.asarray(tok0)
        finished: List[ServeRequest] = []
        with span("serve.chunk.retire", phases):
            for i, job in enumerate(jobs):
                job.off += int(n_valid[i])
                self.kv.advance(job.slot, int(n_valid[i]))  # pages appended
                if fin_pos[i] < 0:
                    continue
                if self.speculate:
                    # drafter now holds the full prompt; emitted tokens
                    # are what each round's resync dispatch will feed it
                    self._draft_len[job.slot] = len(job.tokens)
                self._prefilling.remove(job)
                if self.prefix_cache is not None:
                    # index the finished prompt's full blocks now —
                    # before the request can finish immediately (EOS
                    # first token) and free them down to parked
                    self.prefix_cache.insert(job.tokens,
                                             self.kv.blocks_of(job.slot))
                done = self._install_first_token(job.slot, job.req,
                                                 int(tok0_np[i]), now)
                if done is not None:
                    finished.append(done)
        return finished

    def _admit(self, req: ServeRequest, now: float) -> Optional[ServeRequest]:
        """Prefill one request into a freshly allocated slot. Returns the
        request if it finished immediately (EOS on the first token /
        max_new == 1), else None."""
        batch = {k: jnp.asarray(v) for k, v in req.batch.items()}
        logits, cache = self._prefill(self.params, batch)
        cache = self._prefill_stream.ordered(cache)

        slot = self.kv.alloc(req)
        prompt_len = req.prompt_len
        if self.model.cfg.frontend == "patch_stub":
            prompt_len += self.model.cfg.num_frontend_tokens
        self.kv.insert(slot, cache, length=prompt_len)

        key = jax.random.fold_in(jax.random.PRNGKey(req.seed), req.rid)
        self._state, tok0_dev = self._admit_state(
            self._state, logits, jnp.int32(slot), key,
            jnp.float32(req.temperature), jnp.int32(prompt_len))
        tok0 = int(np.asarray(tok0_dev))
        return self._install_first_token(slot, req, tok0, now)

    def _install_first_token(self, slot: int, req: ServeRequest, tok0: int,
                             now: float) -> Optional[ServeRequest]:
        """Record a freshly-admitted request's first sampled token and
        either finish it immediately (EOS first token / max_new == 1) or
        enter decoding. Shared by monolithic admission and the final
        chunk of a chunked deposit. Returns the request iff finished."""
        req.first_token_time = now
        req.state = "decoding"
        fill = self.eos_id if self.eos_id >= 0 else 0
        out = np.full((req.max_new_tokens,), fill, np.int32)
        out[0] = tok0
        req.generated = 1
        if (0 <= self.eos_id == tok0) or req.max_new_tokens == 1:
            return self._finish(slot, req, out, now)
        if self.role == "prefill":
            # disaggregated fabric: the request does NOT enter this
            # engine's decode pool (never setting _slot_req keeps
            # num_decoding at 0, so no decode dispatch can advance the
            # held state before the transport ships it)
            req.state = "migrating"
            self.ready_handoffs.append(KVHandoff(
                req=req, slot=slot, out=out, length=self.kv.length(slot),
                blocks=self.kv.blocks_of(slot)))
            return None
        self._slot_req[slot] = req
        self._slot_out[slot] = out
        return None

    def _decode_pages(self) -> Dict[str, object]:
        """What decode attention reads, from the host's own lengths: the
        pages of the decoding rows up to the token being decoded
        (``kv_pages``) against the width of the whole table
        (``table_pages``), and the path (``attn_path``)."""
        if self.attn_path is None:
            return {}
        bs = self.kv.block_size
        pages = sum((self.kv.length(s) + bs) // bs
                    for s, r in enumerate(self._slot_req) if r is not None)
        return {"kv_pages": pages,
                "table_pages": self.kv.num_slots * self.kv.max_blocks_per_req,
                "attn_path": self.attn_path}

    def _decode_micro_step(self, now: float,
                           phases: Dict[str, float]) -> List[ServeRequest]:
        with span("serve.decode.dispatch", phases, **self._decode_pages()):
            state = self._decode_stream.ordered(self._state)
            if self.kv_layout == "paged":
                nxt, buf, state = self._decode(self.params, self.kv.buffers,
                                               state, self.kv.tables_device())
            else:
                nxt, buf, state = self._decode(self.params, self.kv.buffers,
                                               state)
            self.kv.swap_buffers(buf)
            self._state = state
        with span("serve.decode.sync", phases):
            nxt_np = np.asarray(nxt)    # the one host sync per micro-step

        finished: List[ServeRequest] = []
        with span("serve.decode.retire", phases):
            for slot in self.kv.live_slots:
                req = self._slot_req[slot]
                if req is None:    # slot still mid-prefill: nothing to read
                    continue
                t = int(nxt_np[slot])
                out = self._slot_out[slot]
                out[req.generated] = t
                req.generated += 1
                self.kv.advance(slot)
                if (0 <= self.eos_id == t) \
                        or req.generated >= req.max_new_tokens:
                    finished.append(self._finish(slot, req, out, now))
                    self._slot_req[slot] = None
                    self._slot_out[slot] = None
        return finished

    def _spec_micro_step(self, now: float,
                         phases: Dict[str, float]) -> List[ServeRequest]:
        """Spec-mode decode micro-step: ONE fused draft–verify round over
        every decoding row (``_spec_round_impl``) replaces up to ``k+1``
        single-token dispatches. The host builds the round's inputs from
        its own bookkeeping (emitted tokens, canonical lengths, drafter
        coverage), dispatches, then syncs the emission buffer once.

        Per row: ``tpos`` (the target's next write position) is the
        row's canonical resident length ``P + g - 1``; the canonical
        context is one token longer (the pending token ``cur``); the
        drafter has consumed ``u = canon - draft_len ∈ {1, 2}`` fewer
        tokens. ``n_draft`` clamps to ``remaining - 1`` so the budget is
        never overdrawn — at ``remaining == 1`` the round degenerates to
        a plain (teacher-forced width-1) decode of the same fixed
        shape."""
        k = self.speculate
        S = self.kv.num_slots
        cur = np.zeros((S,), np.int32)
        prev = np.zeros((S,), np.int32)
        u = np.ones((S,), np.int32)
        sync_pos = np.full((S,), PARK_POS, np.int32)
        tpos = np.full((S,), PARK_POS, np.int32)
        n_draft = np.zeros((S,), np.int32)
        live: List[int] = []
        for slot in self.kv.live_slots:
            req = self._slot_req[slot]
            if req is None:        # slot still mid-prefill: parked row
                continue
            g = req.generated
            out = self._slot_out[slot]
            cur[slot] = out[g - 1]
            prev[slot] = out[g - 2] if g >= 2 else out[g - 1]
            canon = self.kv.length(slot) + 1   # resident + pending token
            uu = canon - int(self._draft_len[slot])
            u[slot] = uu
            sync_pos[slot] = canon - uu
            tpos[slot] = canon - 1
            n_draft[slot] = min(k, req.max_new_tokens - g - 1)
            live.append(slot)
        cost = protocol.speculative_verify_latency(k)
        with span("serve.spec", phases, rows=len(live), k=k) as sp:
            greedy, n_emit, buf, dbuf = self._spec_round(
                self.params, self.draft_params, self.kv.buffers,
                self.draft_kv.buffers, jnp.asarray(cur), jnp.asarray(prev),
                jnp.asarray(u), jnp.asarray(sync_pos), jnp.asarray(tpos),
                jnp.asarray(n_draft), self.kv.tables_device(),
                self.draft_kv.tables_device())
            self.kv.swap_buffers(self._verify_stream.ordered(buf))
            self.draft_kv.swap_buffers(self._draft_stream.ordered(dbuf))
            greedy_np = np.asarray(greedy)     # the one host sync per round
            n_emit_np = np.asarray(n_emit)
            # the round's modeled price is per live row; the measured
            # twin is the fused dispatch + its one host sync
            sp.hop("spec_verify", cost * max(1, len(live)))
        finished: List[ServeRequest] = []
        for slot in live:
            req = self._slot_req[slot]
            out = self._slot_out[slot]
            g = req.generated
            ne = int(n_emit_np[slot])
            em = greedy_np[slot, :ne]
            keep = ne
            if self.eos_id >= 0:
                hits = np.nonzero(em == self.eos_id)[0]
                if hits.size:                  # truncate at first EOS —
                    keep = int(hits[0]) + 1    # post-EOS columns stay
            out[g:g + keep] = em[:keep]        # eos/0-filled
            req.generated = g + keep
            # drafter coverage after this round: the resync + draft
            # steps deposited through position canon + min(n_acc, k) - 1,
            # of which min(n_acc, k-1) past-canon rows are canonical
            canon = self.kv.length(slot) + 1
            self._draft_len[slot] = canon + min(ne - 1, k - 1)
            self.kv.advance(slot, keep)        # accepted rows only: the
            # stale draft rows beyond stay structurally rolled back
            self.scheduler.record_spec_dispatch(
                keep, int(n_draft[slot]), ne - 1, cost)
            if (self.eos_id >= 0 and em[keep - 1] == self.eos_id) \
                    or req.generated >= req.max_new_tokens:
                finished.append(self._finish(slot, req, out, now))
                self._slot_req[slot] = None
                self._slot_out[slot] = None
        return finished

    def _finish(self, slot: int, req: ServeRequest, out: np.ndarray,
                now: float) -> ServeRequest:
        req.output = out
        self.kv.free(slot)
        if self.speculate:
            self.draft_kv.free(slot)       # lockstep with the target row
            self._draft_len[slot] = 0
        # park the freed slot's device position so its decode-vmap row
        # stops writing (stale-slot advance was silently corrupting
        # engine reuse before)
        self._state = self._park_state(self._state, jnp.int32(slot))
        self.scheduler.record_finish(req, now)
        return req

    # -- disaggregated KV handoff (fabric transport surface; paged only) ---
    def take_handoffs(self) -> List[KVHandoff]:
        """Drain the prefill-complete requests awaiting migration. The
        caller (the fabric's transport hop) owns getting each one to a
        decode rank and then calling :meth:`release_handoff` — until
        then this engine keeps the source blocks leased."""
        out, self.ready_handoffs = self.ready_handoffs, []
        return out

    def handoff_state(self, slot: int):
        """The per-request decode-state row migrating with the KV: the
        device-resident (tok, pos, keys, temp) the finalize tail
        installed (pos = prompt_len, tok = the first sampled token,
        keys = the request's advanced PRNG chain)."""
        return {k: self._state[k][slot] for k in
                ("tok", "pos", "keys", "temp")}

    def release_handoff(self, slot: int) -> None:
        """Migration complete: return the source row + blocks to the
        local pools and park the row's device state."""
        self.kv.free(slot)
        self._state = self._park_state(self._state, jnp.int32(slot))

    def begin_import(self, req: ServeRequest):
        """Decode-rank half of the handoff, part 1: claim a request row
        and lease blocks for the request's FULL budget (prompt +
        max_new) *before* the transport copies — the lease is the posted
        receive of the rendezvous discipline. Returns ``(slot,
        dst_blocks)``; the transport writes the migrated prompt KV into
        the first ``blocks_for(prompt_len)`` of ``dst_blocks``."""
        if self.kv_layout != "paged":
            raise ValueError("KV-block import needs kv_layout='paged'")
        slot = self.kv.alloc(req, req.prompt_len + req.max_new_tokens)
        return slot, self.kv.blocks_of(slot)

    def finish_import(self, slot: int, handoff: KVHandoff, state_row,
                      now: float) -> None:
        """Decode-rank half, part 2 (after the transport's waitall):
        install the migrated decode state at ``slot`` and enter the
        request into this engine's decode pool, continuing exactly where
        the prefill rank stopped (generated == 1, next position ==
        prompt_len)."""
        req = handoff.req
        self.kv.advance(slot, handoff.length)    # resident prompt tokens
        self._state = self._import_state(
            self._state, jnp.int32(slot), state_row["tok"],
            state_row["pos"], state_row["keys"], state_row["temp"])
        req.state = "decoding"
        self._slot_req[slot] = req
        self._slot_out[slot] = handoff.out

    def reset(self, *, strict: bool = False,
              preserve_prefix: bool = False) -> None:
        """Return the engine to its post-construction state: every slot
        freed, device-side sampling/position state re-zeroed (positions
        parked), scheduler queues and accounting cleared. Used by traffic
        drivers after jit warm-up so warm requests leave no stale device
        state or accounting behind (compiled programs are kept).

        ``preserve_prefix=True`` (prefix cache only) keeps the parked
        radix index and the device pool content across the reset — the
        warm-cache trial: rows, counters and scheduler state clear, the
        cache stays populated.

        Slots still holding requests are lease leaks: named via
        ``LeaseLeakWarning``, or ``LeaseLeakError`` when ``strict``."""
        S = self.kv.num_slots
        self._state = self._fresh_state(S)
        self._slot_req = [None] * S
        self._slot_out = [None] * S
        self._prefilling.clear()
        self.ready_handoffs.clear()
        if self.prefix_cache is not None and preserve_prefix:
            self.kv.reset_rows(strict=strict)
        else:
            if self.prefix_cache is not None:
                # drop the cache's references first: parked blocks are
                # retention by design, not leaks for the pool to name
                self.prefix_cache.clear()
            self.kv.reset(strict=strict)
        if self.speculate:
            self.draft_kv.reset(strict=strict)
            self._draft_len[:] = 0
        self.scheduler.reset()
        self.peak_live = 0
        self._resident_tok_sum = 0
        self._reserved_tok_sum = 0
        self.prefix_lookups = self.prefix_hits = 0
        self.prefix_hit_tokens = self.prefix_prompt_tokens = 0
        self.prefill_dispatches_saved = self.prefix_cow_clones = 0
        if self.prefix_cache is not None:
            self.prefix_cache.reset_stats()
        # the telemetry subsystem is trial-scoped too: residual pairs and
        # push-registry observations recorded during warm-up (compile-
        # dominated, wildly off-model) must not aggregate into the
        # measured trial — the PR 5 req_log aliasing class, one layer up.
        # Applies to BOTH reset flavors: preserve_prefix=True keeps the
        # radix index warm but the trial's measurements still restart.
        _obs_flush_trial()

    # -- batch-API convenience (parity with StaticEngine.generate) --------
    def generate(self, batch, max_new_tokens: int, *,
                 temperature: float = 0.0, seed: int = 0) -> np.ndarray:
        """Same-arrival batch through the continuous path: split the batch
        into per-row requests, run micro-steps until drained, reassemble
        (B, max_new) in row order."""
        B = batch["tokens"].shape[0]
        reqs = []
        for i in range(B):
            row = {k: np.asarray(v[i:i + 1]) for k, v in batch.items()}
            req = ServeRequest(rid=i, batch=row,
                               max_new_tokens=max_new_tokens,
                               temperature=temperature, seed=seed)
            reqs.append(req)
            self.submit(req, 0.0)
        steps = 0
        chunk_steps = (sum(-(-r.prompt_len // self.prefill_chunk) + 1
                           for r in reqs) if self.prefill_chunk else B)
        limit = (B * (max_new_tokens + 2)) // max(1, self.kv.num_slots) \
            + B * (max_new_tokens + 2) + chunk_steps
        while not self.idle:
            self.step(0.0)
            steps += 1
            if steps > limit:
                raise RuntimeError("continuous generate failed to drain")
        return np.stack([r.output for r in reqs])
