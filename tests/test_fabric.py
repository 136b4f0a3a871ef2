"""Serving fabric (DESIGN.md §10): router dispatch + multi-rank engine
workers over the comm substrate — replicated JSQ placement greedy
token-identical to the single engine, disaggregated prefill/decode with
request-based KV-block migration, transport correctness, dispatch-hop
backpressure, pricing, and reset hygiene across back-to-back trials."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ServeConfig, TrainConfig
from repro.configs import get_smoke_config
from repro.core import protocol
from repro.models.registry import build_model, make_synthetic_batch
from repro.serve import (ContinuousEngine, ServeRequest, ServingFabric,
                         make_trace)
from repro.serve.fabric.placement import make_placement
from repro.serve.fabric.transport import KVBlockTransport
from tests.helpers import run_case

TRAIN = TrainConfig(param_dtype="float32", compute_dtype="float32",
                    loss_chunk=16, attn_chunk_threshold=64, attn_chunk=16,
                    remat=False)

CACHE_LEN = 48 + 8          # longest prompt + max_new ceiling
CHUNK = 16
BLOCK = 8


@pytest.fixture(scope="module")
def bundle():
    cfg = get_smoke_config("gemma-2b")
    model = build_model(cfg, TRAIN, ServeConfig(), tp=1)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _trace_requests(cfg, n=6, seed=0, prompt_len=(16, 48), max_new=(3, 8)):
    trace = make_trace(n, prompt_len=prompt_len, max_new=max_new,
                       arrival="all", seed=seed)
    reqs = []
    for rid, e in enumerate(trace):
        b = make_synthetic_batch(cfg, 1, e.prompt_len, seed=seed + 1000 + rid,
                                 compute_dtype="float32")
        reqs.append(ServeRequest(rid=rid,
                                 batch={"tokens": np.asarray(b["tokens"])},
                                 max_new_tokens=e.max_new, temperature=0.0,
                                 seed=seed, arrival=e.arrival))
    return reqs


def _drain(driveable, reqs, limit=4000):
    for r in reqs:
        driveable.submit(r, 0.0)
    steps = 0
    while not driveable.idle:
        driveable.step(0.0)
        steps += 1
        assert steps < limit, "failed to drain"
    return steps


def _single_engine(model, params, **kw):
    return ContinuousEngine(model, params, cache_len=CACHE_LEN, num_slots=4,
                            prefill_chunk=CHUNK, max_prefill_per_step=2,
                            kv_layout="paged", block_size=BLOCK, **kw)


def _fabric(model, params, placement, **kw):
    return ServingFabric(model, params, ranks=2, placement=placement,
                         cache_len=CACHE_LEN, slots_per_rank=4,
                         prefill_chunk=CHUNK, max_prefill_per_step=2,
                         block_size=BLOCK, **kw)


def _outputs(reqs):
    return [r.output[:r.generated].copy() for r in reqs]


@pytest.fixture(scope="module")
def baseline(bundle):
    cfg, model, params = bundle
    reqs = _trace_requests(cfg)
    _drain(_single_engine(model, params), reqs)
    return _outputs(reqs)


# ---------------------------------------------------------------------------
# placement policies
# ---------------------------------------------------------------------------

def test_placement_roles_and_validation():
    assert make_placement("replicated").roles(3) == ["full"] * 3
    assert make_placement("disagg").roles(3) == ["prefill", "decode",
                                                 "decode"]
    assert make_placement("disagg", n_prefill=2).roles(4) == \
        ["prefill", "prefill", "decode", "decode"]
    with pytest.raises(ValueError):
        make_placement("disagg").roles(1)       # no decode rank left
    with pytest.raises(ValueError):
        make_placement("disagg", n_prefill=2).roles(2)
    with pytest.raises(ValueError):
        make_placement("ring")                  # unknown policy


# ---------------------------------------------------------------------------
# replicated fabric: JSQ data parallelism, token identity
# ---------------------------------------------------------------------------

def test_replicated_token_identity_and_balance(bundle, baseline):
    cfg, model, params = bundle
    fab = _fabric(model, params, "replicated")
    try:
        reqs = _trace_requests(cfg)
        _drain(fab, reqs)
        for want, r in zip(baseline, reqs):
            assert np.array_equal(want, r.output[:r.generated]), r.rid
        # JSQ actually spread the trace over both ranks
        assert sorted({r.rank for r in reqs}) == [0, 1]
        util = fab.stats()["per_rank"]
        assert all(row["role"] == "full" for row in util)
        assert all(row["steps"] > 0 for row in util)
    finally:
        fab.close()


def test_jsq_balances_predicted_cost_not_count(bundle):
    """The JSQ load metric is predicted *work* (protocol-model seconds),
    not request count. On an alternating 16/256-token trace, count-JSQ
    deals strictly alternately — one rank ends up with every long
    prompt; cost-JSQ splits the long prompts across ranks because a
    256-token deposit weighs ~an order of magnitude more than a
    16-token one."""
    import warnings
    cfg, model, params = bundle
    fab = ServingFabric(model, params, ranks=2, placement="replicated",
                        cache_len=320, slots_per_rank=4,
                        prefill_chunk=CHUNK, block_size=16)
    try:
        reqs = []
        for rid in range(8):
            plen = 16 if rid % 2 == 0 else 256
            b = make_synthetic_batch(cfg, 1, plen, seed=3000 + rid,
                                     compute_dtype="float32")
            reqs.append(ServeRequest(
                rid=rid, batch={"tokens": np.asarray(b["tokens"])},
                max_new_tokens=2))
        for r in reqs:
            fab.submit(r, 0.0)
        fab._dispatch(0.0)
        assert all(r.rank >= 0 for r in reqs)       # window 8: all dealt
        w0, w1 = fab.workers
        # load is modeled seconds now; queue_depth keeps the old count
        assert isinstance(w0.load, float)
        assert w0.queue_depth + w1.queue_depth == 8
        # a long deposit costs much more than a short one, decode equal
        heavy = w0.predicted_cost_s(reqs[1])
        light = w0.predicted_cost_s(reqs[0])
        assert heavy > 3 * light
        toks = {w.rank: sum(r.prompt_len for r in reqs if r.rank == w.rank)
                for w in fab.workers}
        heavies = {w.rank: sum(1 for r in reqs
                               if r.rank == w.rank and r.prompt_len == 256)
                   for w in fab.workers}
        # count-JSQ's failure mode: all four 256s on one rank (1024 vs
        # 64 tokens). Cost-JSQ must split them, and each rank's share
        # of prompt work stays within the weight of one long prompt.
        assert min(heavies.values()) >= 1, (heavies, toks)
        assert max(toks.values()) - min(toks.values()) <= 256, toks
        # greedy bound: final modeled loads differ by at most one
        # request's cost
        assert abs(w0.load - w1.load) <= heavy + 1e-12
    finally:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")   # dispatch-only: in flight
            fab.close()


def test_dispatch_window_backpressure(bundle):
    cfg, model, params = bundle
    fab = _fabric(model, params, "replicated", dispatch_window=1)
    try:
        reqs = _trace_requests(cfg, n=6)
        for r in reqs:
            fab.submit(r, 0.0)
        fab._dispatch(0.0)
        # window 1 per rank: at most 2 dispatched, the rest wait at the
        # router (the bounded-buffer discipline, one hop up)
        assert fab.scheduler.num_waiting >= 4
        _drain(fab, [])                      # still drains to completion
        assert all(r.output is not None for r in reqs)
    finally:
        fab.close()


# ---------------------------------------------------------------------------
# disaggregated fabric: prefill/decode split + KV-block migration
# ---------------------------------------------------------------------------

def test_disagg_token_identity_and_migration(bundle, baseline):
    cfg, model, params = bundle
    fab = _fabric(model, params, "disagg")
    try:
        reqs = _trace_requests(cfg)
        _drain(fab, reqs)
        for want, r in zip(baseline, reqs):
            assert np.array_equal(want, r.output[:r.generated]), r.rid
        # every request prefilled on rank 0, decoded on rank 1, with its
        # migration priced by the protocol model
        assert all(r.rank == 0 for r in reqs)
        assert all(r.decode_rank == 1 for r in reqs)
        assert all(r.kv_blocks_moved >= 1 for r in reqs)
        assert all(r.kv_migration_s > 0.0 for r in reqs)
        st = fab.stats()
        assert st["n_migrations"] == len(reqs)
        assert st["blocks_moved"] == sum(r.kv_blocks_moved for r in reqs)
        assert st["kv_migration_modeled_s"] > 0.0
        # the prefill rank never compiled (or ran) a decode dispatch,
        # and every token was produced on the decode rank
        prefill_w, decode_w = fab.workers
        assert prefill_w.engine.decode_compiles == 0
        assert prefill_w.tokens_out == 0
        assert decode_w.tokens_out == sum(r.generated for r in reqs)
        # leases migrated, not leaked: both pools fully free after drain
        assert prefill_w.engine.kv.pool.num_free == \
            prefill_w.engine.kv.pool.num_blocks
        assert decode_w.engine.kv.pool.num_free == \
            decode_w.engine.kv.pool.num_blocks
    finally:
        fab.close()


@pytest.mark.parametrize("placement,role", [("disagg", "decode"),
                                            ("replicated", "full")])
def test_fabric_rejects_unservable_budget(bundle, placement, role):
    """An unservable budget fails at router submit (either placement) —
    not mid-step after the dispatch hop already popped the request."""
    cfg, model, params = bundle
    fab = _fabric(model, params, placement)
    try:
        batch = {"tokens": np.zeros((1, 16), np.int32)}
        req = ServeRequest(rid=0, batch=batch,
                           max_new_tokens=10 * CACHE_LEN)
        with pytest.raises(ValueError, match=f"{role}-rank capacity"):
            fab.submit(req, 0.0)
        assert fab.scheduler.num_waiting == 0    # nothing half-queued
    finally:
        fab.close()


def test_fabric_reset_back_to_back_trials(bundle):
    """Satellite: back-to-back fabric runs must not leak stats — the
    scheduler's rid-keyed arrival/accounting maps are cleared by
    reset(), so trial 2's percentiles cover exactly trial 2."""
    cfg, model, params = bundle
    fab = _fabric(model, params, "disagg")
    try:
        reqs1 = _trace_requests(cfg, n=4, seed=1)
        _drain(fab, reqs1)
        assert fab.stats()["n"] == 4
        assert len(fab.scheduler.req_log) == 4
        fab.reset()
        assert fab.scheduler.req_log == {}
        assert fab.stats().get("n", 0.0) == 0.0
        assert all(w.total_steps == 0 for w in fab.workers)
        # same rids again (every trial restarts at rid 0)
        reqs2 = _trace_requests(cfg, n=4, seed=2)
        _drain(fab, reqs2)
        st = fab.stats()
        assert st["n"] == 4
        assert st["n_migrations"] == 4
        assert sorted(fab.scheduler.req_log) == [0, 1, 2, 3]
        assert all(fab.scheduler.req_log[r.rid] is r for r in reqs2)
    finally:
        fab.close()


# ---------------------------------------------------------------------------
# transport + engine role plumbing
# ---------------------------------------------------------------------------

class _StubModel:
    @staticmethod
    def init_paged_cache(num_blocks, block_size, num_rows=0):
        shape = (2, num_blocks, block_size, 1, 2)
        return {"k": jnp.zeros(shape, jnp.float32),
                "v": jnp.zeros(shape, jnp.float32)}


def test_transport_moves_exact_blocks():
    from repro.core.comm import threadcomm_init
    from repro.core.compat import make_mesh
    from repro.serve.block_pool import PagedKVCache

    mesh = make_mesh((1,), ("serve",))
    comm = threadcomm_init(mesh, process_axes=(), thread_axes=("serve",))
    comm.start()
    try:
        src = PagedKVCache(_StubModel, num_blocks=6, block_size=4,
                           num_slots=2, max_blocks_per_req=4)
        dst = PagedKVCache(_StubModel, num_blocks=6, block_size=4,
                           num_slots=2, max_blocks_per_req=4)
        # fill the src pool with distinguishable block contents
        marks = jnp.arange(6, dtype=jnp.float32)[None, :, None, None, None]
        src.swap_buffers({"k": jnp.broadcast_to(
            marks, src.buffers["k"].shape).astype(jnp.float32) + 1.0,
            "v": jnp.broadcast_to(
            marks, src.buffers["v"].shape).astype(jnp.float32) + 100.0})
        tp = KVBlockTransport(comm)
        cost = tp.migrate(src, dst, [4, 1], [0, 3])
        out = np.asarray(dst.buffers["k"])
        assert np.all(out[:, 0] == 5.0)          # src block 4 -> dst 0
        assert np.all(out[:, 3] == 2.0)          # src block 1 -> dst 3
        assert np.all(out[:, 1] == 0.0)          # untouched
        assert np.all(np.asarray(dst.buffers["v"])[:, 0] == 104.0)
        assert cost > 0.0
        assert tp.n_blocks_moved == 2 and tp.n_migrations == 1
        assert tp.bytes_moved == 2 * tp.block_nbytes(src)
        with pytest.raises(ValueError, match="disagree"):
            tp.migrate(src, dst, [0, 1], [2])
    finally:
        comm.finish()
        comm.free()


def test_engine_role_validation(bundle):
    cfg, model, params = bundle
    with pytest.raises(ValueError, match="role"):
        _single_engine(model, params, role="router")
    with pytest.raises(ValueError, match="paged"):
        ContinuousEngine(model, params, cache_len=CACHE_LEN, num_slots=2,
                         kv_layout="slot", role="prefill")


def test_prefill_role_leases_prompt_only(bundle):
    """A prefill-rank engine leases blocks for the prompt alone (the
    generated tokens' KV lands on the decode rank), so its pool admits
    far more concurrent prefills than a full engine could."""
    cfg, model, params = bundle
    eng = _single_engine(model, params, role="prefill")
    batch = {"tokens": np.zeros((1, 16), np.int32)}
    req = ServeRequest(rid=7, batch=batch, max_new_tokens=32)
    assert eng._token_budget(req) == 16
    eng.submit(req, 0.0)
    steps = 0
    while not eng.ready_handoffs:
        eng.step(0.0)
        steps += 1
        assert steps < 50
    h = eng.ready_handoffs[0]
    assert h.req is req and req.state == "migrating"
    assert h.length == 16
    assert len(h.blocks) == -(-16 // BLOCK)      # prompt blocks only
    assert req.generated == 1
    # the migrating decode state is coherent: next position is the
    # prompt end, and the device-held next-input token is the first
    # sampled token recorded in the output buffer
    state = eng.handoff_state(h.slot)
    assert int(np.asarray(state["pos"])) == 16
    assert int(np.asarray(state["tok"]).ravel()[0]) == int(h.out[0])
    assert eng.num_decoding == 0                 # never enters decode here
    # release returns the lease
    taken = eng.take_handoffs()
    assert taken == [h] and not eng.ready_handoffs
    eng.release_handoff(h.slot)
    assert eng.kv.pool.num_free == eng.kv.pool.num_blocks


def test_ranks_run_on_their_own_devices():
    """On four devices rank i's params and KV pool live on device i,
    under both placements (disagg copies KV blocks device to device),
    token-identical to one engine on device 0."""
    run_case("fabric_rank_devices", ndev=4, timeout=600)


# ---------------------------------------------------------------------------
# protocol pricing
# ---------------------------------------------------------------------------

def test_kv_migration_latency_pricing():
    m = protocol.HostModel()
    one = protocol.kv_migration_latency(8192, 8192, m)
    assert one == pytest.approx(
        m.t_handshake + protocol.interthread_latency(8192, m))
    four = protocol.kv_migration_latency(4 * 8192, 8192, m)
    assert four == pytest.approx(
        m.t_handshake + 4 * protocol.interthread_latency(8192, m))
    # a partial tail block is priced at its own (smaller) payload
    tail = protocol.kv_migration_latency(8192 + 100, 8192, m)
    assert one < tail < protocol.kv_migration_latency(2 * 8192, 8192, m)
    with pytest.raises(ValueError):
        protocol.kv_migration_latency(8192, 0, m)
