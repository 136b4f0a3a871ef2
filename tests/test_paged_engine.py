"""Paged-KV serving: deterministic block-pool/PagedKVCache behavior and
the paged ContinuousEngine — greedy token parity against both the static
baseline and the slot-pool engine (the acceptance bar for the paged
refactor), block-gated admission deferral, early-EOS lease release, and
misuse errors naming the owner."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ServeConfig, TrainConfig
from repro.configs import get_smoke_config
from repro.models.registry import build_model, make_synthetic_batch
from repro.serve import (BlockPool, ContinuousEngine, PagedKVCache,
                        SlotError, SlotKVCache, StaticEngine)

TRAIN = TrainConfig(param_dtype="float32", compute_dtype="float32",
                    loss_chunk=16, attn_chunk_threshold=64, attn_chunk=16,
                    remat=False)


def _bundle(arch="gemma-2b", seed=0):
    cfg = get_smoke_config(arch)
    model = build_model(cfg, TRAIN, ServeConfig(), tp=1)
    return cfg, model, model.init(jax.random.PRNGKey(seed))


def _prompt(cfg, B=4, S=8):
    batch = make_synthetic_batch(cfg, B, S, compute_dtype="float32")
    return {"tokens": batch["tokens"]}


# ---------------------------------------------------------------------------
# BlockPool / PagedKVCache (deterministic; property tests need hypothesis
# and live in tests/test_block_pool.py)
# ---------------------------------------------------------------------------

def test_pool_alloc_free_roundtrip():
    pool = BlockPool(num_blocks=8, block_size=16)
    blocks = pool.alloc(5, "req0")
    assert len(set(blocks)) == 5
    assert pool.num_free == 3 and pool.num_live == 5
    assert all(pool.refcount(b) == 1 for b in blocks)
    assert all(pool.owner(b) == "req0" for b in blocks)
    pool.free(blocks)
    assert pool.num_free == 8 and pool.num_live == 0


def test_pool_refcount_shared_block():
    pool = BlockPool(num_blocks=4, block_size=16)
    blocks = pool.alloc(2, "a")
    shared = blocks[0]
    pool.ref(shared)                       # second lease (shared prefix)
    pool.free(blocks)                      # first owner done
    assert pool.refcount(shared) == 1      # still live for the sharer
    assert pool.num_free == 3
    pool.free([shared])
    assert pool.num_free == 4


def test_pool_misuse_errors_name_owner():
    pool = BlockPool(num_blocks=2, block_size=16)
    blocks = pool.alloc(1, "req-42")
    pool.free(blocks)
    with pytest.raises(SlotError, match="req-42"):
        pool.free(blocks)
    with pytest.raises(SlotError, match="exhausted"):
        pool.alloc(3, "big")
    with pytest.raises(SlotError, match="free block"):
        pool.ref(blocks[0])


class _StubModel:
    @staticmethod
    def init_paged_cache(num_blocks, block_size, dtype=None,
                        num_rows=0):
        return {"k": np.zeros((1, num_blocks, block_size, 1, 1)),
                "v": np.zeros((1, num_blocks, block_size, 1, 1))}


def test_paged_cache_lease_overrun_and_double_free():
    kv = PagedKVCache(_StubModel(), num_blocks=16, block_size=4,
                      num_slots=4, max_blocks_per_req=8)
    row = kv.alloc("req7", 5)          # 2 blocks of 4 = 8 token lease
    kv.advance(row, 8)
    with pytest.raises(SlotError, match="overran its lease"):
        kv.advance(row, 1)
    kv.free(row)
    with pytest.raises(SlotError, match="req7"):
        kv.free(row)


def test_paged_cache_admission_gates():
    kv = PagedKVCache(_StubModel(), num_blocks=4, block_size=4,
                      num_slots=2, max_blocks_per_req=4)
    assert kv.can_admit(16)            # 4 blocks, exactly the pool
    r = kv.alloc("a", 4)
    assert not kv.can_admit(16)        # only 3 blocks left
    assert kv.can_admit(12)
    with pytest.raises(SlotError, match="max_blocks_per_req"):
        kv.can_admit(17)               # would exceed the per-request cap
    kv.free(r)
    assert kv.can_admit(16)


def test_host_length_bookkeeping_is_int32_both_pools():
    """Both pools keep host lengths in int32 — the device position dtype —
    and both name the last owner on double free."""
    kv = PagedKVCache(_StubModel(), num_blocks=16, block_size=4,
                      num_slots=4, max_blocks_per_req=8)
    row = kv.alloc("a", 3)
    kv.advance(row, 3)
    assert kv.lengths.dtype == np.int32 and kv.length(row) == 3

    class _SlotStub:
        @staticmethod
        def init_cache(batch, cache_len):
            return {"k": jnp.zeros((batch, cache_len, 1, 1))}

    slots = SlotKVCache(_SlotStub(), cache_len=8, num_slots=2)
    s = slots.alloc("owner-a")
    slots.advance(s, 5)
    assert slots.lengths.dtype == np.int32 and slots.length(s) == 5
    slots.free(s)
    with pytest.raises(SlotError, match="owner-a"):
        slots.free(s)


# ---------------------------------------------------------------------------
# paged engine parity (acceptance: token-identical to slot pool + static)
# ---------------------------------------------------------------------------

def _paged(model, params, **kw):
    kw.setdefault("cache_len", 24)
    kw.setdefault("num_slots", 4)
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("block_size", 8)
    return ContinuousEngine(model, params, **kw)


def test_paged_greedy_parity_same_arrival_batch():
    cfg, model, params = _bundle()
    prompt = _prompt(cfg, B=4, S=8)
    static = StaticEngine(model, params, cache_len=24).generate(prompt, 12)
    slot = ContinuousEngine(model, params, cache_len=24, num_slots=4,
                            prefill_chunk=4).generate(prompt, 12)
    paged = _paged(model, params).generate(prompt, 12)
    assert np.array_equal(static, paged)
    assert np.array_equal(slot, paged)


def test_paged_parity_multi_chunk_prompts():
    """Prompts spanning several chunks AND several blocks (chunk != block
    size, neither dividing the prompt)."""
    cfg, model, params = _bundle()
    prompt = _prompt(cfg, B=3, S=21)
    static = StaticEngine(model, params, cache_len=32).generate(prompt, 8)
    paged = _paged(model, params, cache_len=32, num_slots=3, prefill_chunk=6,
                   block_size=4).generate(prompt, 8)
    assert np.array_equal(static, paged)


def test_paged_parity_block_recycling():
    """More requests than the pool holds at once: blocks recycle across
    requests and stale pages of previous owners must not leak into
    attention (structural masking)."""
    cfg, model, params = _bundle()
    prompt = _prompt(cfg, B=4, S=8)
    static = StaticEngine(model, params, cache_len=24).generate(prompt, 10)
    # pool: 6 blocks of 8 = 48 tokens; each request needs 3 blocks (8+10
    # tokens) -> at most 2 in flight, 4 requests recycle the pool
    paged = _paged(model, params, num_slots=2, num_blocks=6,
                   ).generate(prompt, 10)
    assert np.array_equal(static, paged)


def test_paged_admission_defers_on_blocks_not_rows():
    """Rows are plentiful; blocks are scarce: the engine must defer
    admission (head-of-line) and the deferral shows in the scheduler's
    block-deferral counter."""
    cfg, model, params = _bundle()
    prompt = _prompt(cfg, B=4, S=8)
    eng = _paged(model, params, num_slots=4, num_blocks=3)  # 1 req at a time
    out = eng.generate(prompt, 6)
    assert out.shape == (4, 6)
    assert eng.scheduler.n_block_deferrals > 0
    assert eng.kv.num_live == 0 and eng.kv.num_free_blocks == 3


def test_paged_eos_frees_lease_early():
    cfg, model, params = _bundle()
    prompt = _prompt(cfg, B=2, S=8)
    ref = StaticEngine(model, params, cache_len=40).generate(prompt, 16)
    eos = int(ref[0, 3])               # force an early EOS for row 0
    eng = _paged(model, params, cache_len=40, num_slots=2, eos_id=eos)
    out = eng.generate(prompt, 16)
    assert eng.kv.num_live == 0
    assert eng.kv.num_free_blocks == eng.kv.pool.num_blocks
    hit = np.flatnonzero(out[0] == eos)
    assert hit.size and (out[0, int(hit[0]):] == eos).all()


def test_paged_engine_reset_restores_pool():
    cfg, model, params = _bundle()
    eng = _paged(model, params)
    eng.generate(_prompt(cfg, B=2, S=8), 4)
    eng.reset()
    assert eng.kv.num_live == 0
    assert eng.kv.num_free_blocks == eng.kv.pool.num_blocks
    assert eng.peak_live == 0 and eng.scheduler.num_waiting == 0
    out = eng.generate(_prompt(cfg, B=2, S=8), 4)   # reusable after reset
    assert out.shape == (2, 4)


def test_pinned_engine_compiles_once_across_reset():
    """An engine pinned to a device (a fabric rank on its chip) keeps
    params, pool and decode state committed there across reset, so a
    second identical run after reset compiles nothing."""
    cfg, model, params = _bundle()
    dev = jax.devices()[0]
    eng = _paged(model, jax.device_put(params, dev), device=dev)
    prompt = _prompt(cfg, B=2, S=8)
    first = eng.generate(prompt, 4)
    eng.reset()
    compiles = []

    def listener(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        second = eng.generate(prompt, 4)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert np.array_equal(first, second)
    assert compiles == []
    for leaf in jax.tree_util.tree_leaves((eng.kv.buffers, eng._state)):
        assert leaf.devices() == {dev}


def test_paged_oversized_request_rejected_at_submit():
    """A request whose prompt+max_new can never fit its block table must
    fail loudly at submit — not crash the serve loop from the admission
    gate once it reaches the queue head."""
    from repro.serve import ServeRequest
    cfg, model, params = _bundle()
    # capacity: ceil(24/8)=3 blocks x 8 = 24 tokens; 8 + 20 = 28 > 24
    eng = _paged(model, params)
    batch = make_synthetic_batch(cfg, 1, 8, compute_dtype="float32")
    req = ServeRequest(rid=0, batch={"tokens": np.asarray(batch["tokens"])},
                       max_new_tokens=20)
    with pytest.raises(ValueError, match="admittable capacity"):
        eng.submit(req)
    assert eng.scheduler.num_waiting == 0      # nothing poisoned the queue

    # lease fits the per-request table but NOT the whole pool: must also
    # be rejected at submit, not deferred forever (admission livelock)
    small = _paged(model, params, num_slots=1, num_blocks=2)   # 16 tokens
    req2 = ServeRequest(rid=1, batch={"tokens": np.asarray(batch["tokens"])},
                        max_new_tokens=12)                     # needs 20
    with pytest.raises(ValueError, match="admittable capacity"):
        small.submit(req2)


def test_paged_requires_chunked_deposit():
    cfg, model, params = _bundle()
    with pytest.raises(ValueError, match="chunk"):
        ContinuousEngine(model, params, cache_len=24, num_slots=2,
                         prefill_chunk=0, kv_layout="paged")


def test_paged_ssm_family_runs_with_parity():
    """SSM families run the paged path (carried state threaded through
    row-aligned pool leaves — DESIGN.md §13) token-identically to the
    static baseline; the old dense-only gate is gone."""
    cfg, mamba_model, mamba_params = _bundle("mamba2-370m")
    prompt = _prompt(cfg, B=2, S=8)
    static = StaticEngine(mamba_model, mamba_params,
                          cache_len=24).generate(prompt, 6)
    eng = ContinuousEngine(mamba_model, mamba_params, cache_len=24,
                           num_slots=2, prefill_chunk=8,
                           kv_layout="paged", block_size=4)
    assert np.array_equal(static, eng.generate(prompt, 6))


def test_paged_temperature_determinism():
    """Same seed + temperature: slot and paged engines draw identical
    tokens (per-request key chains are layout-independent)."""
    cfg, model, params = _bundle()
    prompt = _prompt(cfg, B=3, S=8)
    a = ContinuousEngine(model, params, cache_len=24, num_slots=3,
                         prefill_chunk=4).generate(
        prompt, 10, temperature=0.7, seed=3)
    b = _paged(model, params, num_slots=3).generate(
        prompt, 10, temperature=0.7, seed=3)
    assert np.array_equal(a, b)
