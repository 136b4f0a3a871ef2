"""Universal family serving (DESIGN.md §13): every registry family runs
the continuous + paged path token-identically to its static monolithic
baseline, and the state-threaded chunk contract resumes recurrent scans
bit-exactly at any chunk boundary.

Three layers of evidence:

* chunked deposit vs monolithic prefill produce the same carried-state
  pytree (to float32 matmul rounding) and the same first token (to a
  logit margin) for SSM and hybrid
  — a hypothesis property over random prompt lengths / chunk sizes
  (``ssm_chunk`` multiples) when hypothesis is installed, plus a
  deterministic seeded sweep that always runs;
* engine-level token identity for all four non-dense families
  (MoE, SSM, hybrid, enc-dec) through ``ContinuousEngine`` with
  ``kv_layout="paged"`` and chunked prefill;
* one carried-state family end-to-end through the replicated serving
  fabric (the router must not perturb a single sampled token).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ServeConfig, TrainConfig
from repro.configs import get_smoke_config
from repro.models.registry import build_model, make_synthetic_batch
from repro.serve import ContinuousEngine, ServeRequest, StaticEngine
from repro.serve.fabric.router import ServingFabric

TRAIN = TrainConfig(param_dtype="float32", compute_dtype="float32",
                    loss_chunk=16, attn_chunk_threshold=64, attn_chunk=16,
                    remat=False)

_BUNDLES = {}


def _bundle(arch):
    if arch not in _BUNDLES:
        cfg = get_smoke_config(arch)
        model = build_model(cfg, TRAIN, ServeConfig(), tp=1)
        _BUNDLES[arch] = (cfg, model, model.init(jax.random.PRNGKey(0)))
    return _BUNDLES[arch]


def _prompt(cfg, B, S, seed=0):
    batch = make_synthetic_batch(cfg, B, S, seed=seed,
                                 compute_dtype="float32")
    return {k: np.asarray(v) for k, v in batch.items() if k != "labels"}


# ---------------------------------------------------------------------------
# chunked == monolithic: carried state + first token (SSM / hybrid)
# ---------------------------------------------------------------------------

def _chunked_deposit(model, params, tokens, chunk, cache_len):
    """Drive the slot chunk step over a whole prompt by hand (what the
    engine's prefill ladder does) and return (first token, cache)."""
    cache = model.init_cache(1, cache_len)
    S = tokens.shape[1]
    logits = None
    for pos0 in range(0, S, chunk):
        n_valid = min(chunk, S - pos0)
        tok = np.zeros(chunk, np.int32)
        tok[:n_valid] = tokens[0, pos0:pos0 + n_valid]
        logits, cache = model.prefill_chunk(
            params, cache, jnp.asarray(tok),
            jnp.int32(pos0), jnp.int32(n_valid))
    return int(jnp.argmax(logits)), cache


#: Carried state is compared to a tolerance, not bit for bit. The conv
#: leaf is raw ``in_proj`` output, and a float32 matmul over a C-row
#: chunk accumulates in a different order than one over the whole
#: S-row prompt (the backend tiles by M), so rows differ by a few ulps
#: (measured <= 1.4e-6 absolute on CPU). The hybrid's attention layers
#: add the same kind of reassociation noise. A logic error (a lost or
#: shifted row, a wrong decay grid) moves values by O(1e-2) or more.
STATE_RTOL, STATE_ATOL = 1e-4, 1e-5
#: First-token logits agree to ~2.5e-6 (measured); a chunked token is
#: accepted when its logit is within this margin of the reference
#: argmax, so a near-tie between random-init logits cannot flip the test.
LOGIT_MARGIN = 1e-4


def _assert_token_within_margin(tok, logits_ref, what):
    ref = np.asarray(logits_ref, np.float32).ravel()
    assert ref[tok] >= ref.max() - LOGIT_MARGIN, (
        what, tok, int(ref.argmax()), float(ref.max() - ref[tok]))


def _assert_chunked_matches_monolithic(arch, S, chunk, seed):
    cfg, model, params = _bundle(arch)
    m = model.capabilities.chunk_multiple
    cache_len = 4 * m
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab_size, (1, S)).astype(np.int32)
    leaves = model.capabilities.state_leaves
    what = (arch, S, chunk, seed)

    logits_m, cache_m = model.prefill(params, {"tokens": jnp.asarray(tokens)},
                                      cache_len)
    tok_c, cache_c = _chunked_deposit(model, params, tokens, chunk, cache_len)
    _assert_token_within_margin(tok_c, logits_m[0], what)

    # the state-threading contract: resuming the scan at a DIFFERENT
    # chunk grid deposits the same carried state (to matmul rounding)
    other = 2 * m if chunk == m else m
    tok_o, cache_o = _chunked_deposit(model, params, tokens, other, cache_len)
    _assert_token_within_margin(tok_o, logits_m[0], what)
    for leaf in leaves:
        np.testing.assert_allclose(
            np.asarray(cache_c[leaf]), np.asarray(cache_o[leaf]),
            rtol=STATE_RTOL, atol=STATE_ATOL,
            err_msg=f"carried-state leaf {leaf!r} depends on the chunk "
                    f"grid ({arch}, S={S}, {chunk} vs {other}, seed={seed})")

    # vs the monolithic oracle: same tolerance and the same reason
    for leaf in leaves:
        np.testing.assert_allclose(
            np.asarray(cache_m[leaf]), np.asarray(cache_c[leaf]),
            rtol=STATE_RTOL, atol=STATE_ATOL,
            err_msg=f"carried-state leaf {leaf!r} diverged beyond float32 "
                    f"matmul rounding ({arch}, S={S}, chunk={chunk}, "
                    f"seed={seed})")


@pytest.mark.parametrize("arch", ["mamba2-370m", "hymba-1.5b"])
def test_chunked_prefill_state_and_first_token_sweep(arch):
    """Deterministic sweep of the chunk-resume invariant: prompt lengths
    off the chunk grid, chunk sizes at 1x/2x the family multiple."""
    m = _bundle(arch)[1].capabilities.chunk_multiple
    for seed, (S, k) in enumerate([(1, 1), (m, 1), (m + 3, 1),
                                   (2 * m, 2), (3 * m - 1, 1),
                                   (2 * m + 5, 2)]):
        _assert_chunked_matches_monolithic(arch, S, k * m, seed)


try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                  # pragma: no cover
    pass
else:
    @pytest.mark.parametrize("arch", ["mamba2-370m", "hymba-1.5b"])
    @given(data=st.data())
    @settings(max_examples=8, deadline=None)
    def test_chunked_prefill_state_and_first_token_property(arch, data):
        cfg, model, params = _bundle(arch)
        m = model.capabilities.chunk_multiple
        S = data.draw(st.integers(1, 3 * m), label="prompt_len")
        chunk = m * data.draw(st.integers(1, 3), label="chunk_multiples")
        seed = data.draw(st.integers(0, 2 ** 16), label="seed")
        _assert_chunked_matches_monolithic(arch, S, chunk, seed)


# ---------------------------------------------------------------------------
# engine-level: four non-dense families, paged + chunked vs static
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "mamba2-370m",
                                  "hymba-1.5b", "whisper-tiny"])
def test_family_paged_chunked_token_identity(arch):
    cfg, model, params = _bundle(arch)
    prompt = _prompt(cfg, B=3, S=24)
    static = StaticEngine(model, params, cache_len=32).generate(prompt, 6)
    eng = ContinuousEngine(model, params, cache_len=32, num_slots=4,
                           prefill_chunk=16, kv_layout="paged",
                           block_size=8)
    out = eng.generate(prompt, 6)
    assert np.array_equal(np.asarray(static), np.asarray(out)), arch


# ---------------------------------------------------------------------------
# carried-state family through the replicated fabric
# ---------------------------------------------------------------------------

def test_ssm_family_through_replicated_fabric():
    cfg, model, params = _bundle("mamba2-370m")
    assert model.capabilities.carried_state

    def reqs_for():
        out = []
        for rid in range(4):
            b = _prompt(cfg, B=1, S=24, seed=1000 + rid)
            out.append(ServeRequest(rid=rid, batch=b, max_new_tokens=4,
                                    temperature=0.0, seed=0))
        return out

    def drain(target, reqs):
        for r in reqs:
            target.submit(r, 0.0)
        guard = 0
        while not target.idle:
            target.step(0.0)
            guard += 1
            assert guard < 2000, "failed to drain"
        return [r.output[:r.generated].copy() for r in reqs]

    ref = drain(ContinuousEngine(model, params, cache_len=32, num_slots=4,
                                 prefill_chunk=16, kv_layout="paged",
                                 block_size=8), reqs_for())
    fab = ServingFabric(model, params, ranks=2, placement="replicated",
                        cache_len=32, slots_per_rank=2, prefill_chunk=16,
                        block_size=8)
    try:
        out = drain(fab, reqs_for())
        assert all(np.array_equal(a, b) for a, b in zip(ref, out))
        # the router's dispatch-hop scheduler prices the carried-state
        # handoff (capability-driven, DESIGN.md §13)
        assert fab.scheduler.state_bytes > 0
    finally:
        fab.close()


def test_disagg_refuses_carried_state_family():
    """KV-block migration would strand recurrent state at the prefill
    rank: the fabric refuses up front, naming the capability."""
    cfg, model, params = _bundle("mamba2-370m")
    with pytest.raises(ValueError, match="kv_migration"):
        ServingFabric(model, params, ranks=2, placement="disagg",
                      cache_len=32, slots_per_rank=2, prefill_chunk=16,
                      block_size=8)
