"""Pallas kernel validation: interpret=True execution vs pure-jnp oracles,
swept over shapes and dtypes (per-kernel allclose requirement)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.msgq import ops as msgq_ops
from repro.kernels.msgq.ops import copy_accounting
from repro.kernels.msgq.ref import msgq_copy_ref
from repro.kernels.paged_attention import ops as paged_ops
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.kernels.ssd_scan.ref import ssd_scan_ref

# the wrappers compile for the TPU by default; here every kernel body
# runs in the Pallas interpreter on the CPU
flash_attention = functools.partial(flash_ops.flash_attention, interpret=True)
msgq_copy = functools.partial(msgq_ops.msgq_copy, interpret=True)
paged_attention = functools.partial(paged_ops.paged_attention, interpret=True)
ssd_scan = functools.partial(ssd_ops.ssd_scan, interpret=True)


# ---------------------------------------------------------------------------
# msgq
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nelems", [17, 256, 1024, 5000, 1 << 15])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_msgq_copy_matches_ref(nelems, dtype):
    if jnp.issubdtype(dtype, jnp.integer):
        msg = jnp.arange(nelems, dtype=dtype)
    else:
        msg = jax.random.normal(jax.random.PRNGKey(0), (nelems,)).astype(dtype)
    out, proto = msgq_copy(msg)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(msgq_copy_ref(msg)))
    nbytes = nelems * msg.dtype.itemsize
    assert proto == ("one_copy" if nbytes > 4096 else "eager_fast")


@pytest.mark.parametrize("force", ["eager", "one_copy"])
def test_msgq_forced_protocols(force):
    msg = jax.random.normal(jax.random.PRNGKey(1), (3000,))
    out, proto = msgq_copy(msg, force_protocol=force)
    assert proto == force
    np.testing.assert_array_equal(np.asarray(out), np.asarray(msg))


def test_msgq_accounting():
    # eager moves 2x the bytes; 1-copy moves 1x (the Fig.3 bandwidth story)
    e = copy_accounting(1 << 20, "eager")
    o = copy_accounting(1 << 20, "one_copy")
    assert e["bytes_moved"] == 2 * o["bytes_moved"]
    assert e["dma_issues"] == 2 * o["dma_issues"]


def test_msgq_multidim_roundtrip():
    msg = jax.random.normal(jax.random.PRNGKey(2), (7, 33, 5))
    out, _ = msgq_copy(msg)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(msg))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,Hkv,Sq,Sk,hd,bq,bk", [
    (1, 2, 2, 64, 64, 16, 16, 16),
    (2, 4, 2, 128, 128, 32, 64, 32),     # GQA
    (1, 8, 1, 64, 64, 64, 32, 32),       # MQA
    (2, 2, 2, 96, 96, 16, 32, 32),       # non-power-of-two seq
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_matches_ref_causal(B, H, Hkv, Sq, Sk, hd, bq, bk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, Sk, Hkv, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Sk, Hkv, hd)).astype(dtype)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    ref = flash_attention_ref(q.transpose(0, 2, 1, 3),
                              k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3), causal=True
                              ).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [8, 32])
def test_flash_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 16))
    k = jax.random.normal(ks[1], (1, 64, 2, 16))
    v = jax.random.normal(ks[2], (1, 64, 2, 16))
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=16, block_k=16)
    ref = flash_attention_ref(q.transpose(0, 2, 1, 3),
                              k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3), causal=True,
                              window=window).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_q_offset_decode_continuation():
    """q_offset places queries mid-sequence (prefill continuation)."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    k = jax.random.normal(ks[1], (1, 128, 2, 16))
    v = jax.random.normal(ks[2], (1, 128, 2, 16))
    q = jax.random.normal(ks[0], (1, 32, 2, 16))
    out = flash_attention(q, k, v, causal=True, q_offset=96,
                          block_q=16, block_k=32)
    ref = flash_attention_ref(q.transpose(0, 2, 1, 3),
                              k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3), causal=True,
                              q_offset=96).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_matches_model_chunked_attention():
    """Cross-validate the kernel against the model's lax.scan chunked path
    (the two production implementations must agree)."""
    from repro.models.layers import chunked_attention
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 32))
    k = jax.random.normal(ks[1], (2, 128, 4, 32))
    v = jax.random.normal(ks[2], (2, 128, 4, 32))
    pos = jnp.arange(128)
    a = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    b = chunked_attention(q, k, v, q_pos=pos, k_pos=pos, causal=True,
                          chunk_q=32, chunk_k=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# paged attention
# ---------------------------------------------------------------------------

def _paged_inputs(B, H, Hkv, hd, P, bs, NB, seed=0, dtype=jnp.float32):
    """Random pool + per-request tables of distinct blocks + lengths that
    land strictly inside each table's capacity."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, H, hd)).astype(dtype)
    kp = jax.random.normal(ks[1], (P, bs, Hkv, hd)).astype(dtype)
    vp = jax.random.normal(ks[2], (P, bs, Hkv, hd)).astype(dtype)
    rng = np.random.default_rng(seed)
    perm = rng.permutation(P)
    bt = np.full((B, NB), -1, np.int32)
    ln = np.zeros((B,), np.int32)
    used = 0
    for b in range(B):
        nb = int(rng.integers(1, NB + 1))
        bt[b, :nb] = perm[used:used + nb]
        used += nb
        ln[b] = int(rng.integers((nb - 1) * bs + 1, nb * bs + 1))
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(ln)


@pytest.mark.parametrize("B,H,Hkv,hd,P,bs,NB", [
    (1, 2, 2, 16, 6, 8, 3),
    (3, 4, 2, 32, 16, 16, 4),            # GQA
    (2, 8, 1, 64, 12, 8, 4),             # MQA
    (4, 4, 4, 16, 24, 4, 6),             # many small blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_matches_ref(B, H, Hkv, hd, P, bs, NB, dtype):
    q, kp, vp, bt, ln = _paged_inputs(B, H, Hkv, hd, P, bs, NB, dtype=dtype)
    out = paged_attention(q, kp, vp, bt, ln)
    ref = paged_attention_ref(q, kp, vp, bt, ln)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [4, 16])
def test_paged_attention_sliding_window(window):
    q, kp, vp, bt, ln = _paged_inputs(2, 4, 2, 16, 10, 8, 4, seed=1)
    out = paged_attention(q, kp, vp, bt, ln, window=window)
    ref = paged_attention_ref(q, kp, vp, bt, ln, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_paged_attention_softcap():
    q, kp, vp, bt, ln = _paged_inputs(2, 4, 2, 16, 10, 8, 4, seed=2)
    out = paged_attention(q, kp, vp, bt, ln, softcap=20.0)
    ref = paged_attention_ref(q, kp, vp, bt, ln, softcap=20.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_paged_attention_identity_table_matches_dense():
    """With an identity block table (block i at pool slot i) the paged
    kernel is plain causal decode attention — cross-validate against the
    flash attention oracle at the last position."""
    B, H, Hkv, hd, bs, NB = 2, 4, 2, 32, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    Sk = NB * bs
    ln = jnp.array([Sk, Sk - 5], jnp.int32)
    kp = jax.random.normal(ks[1], (NB * B, bs, Hkv, hd))
    vp = jax.random.normal(ks[2], (NB * B, bs, Hkv, hd))
    q = jax.random.normal(ks[0], (B, H, hd))
    bt = jnp.arange(B * NB, dtype=jnp.int32).reshape(B, NB)
    out = paged_attention(q, kp, vp, bt, ln)
    # dense view: request b's tokens are pool blocks [b*NB, (b+1)*NB)
    kd = kp.reshape(B, Sk, Hkv, hd).transpose(0, 2, 1, 3)
    vd = vp.reshape(B, Sk, Hkv, hd).transpose(0, 2, 1, 3)
    for b in range(B):
        L_b = int(ln[b])
        ref = flash_attention_ref(
            q[b:b + 1, :, None, :], kd[b:b + 1, :, :L_b], vd[b:b + 1, :, :L_b],
            causal=True, q_offset=L_b - 1)[:, :, 0]
        np.testing.assert_allclose(np.asarray(out[b:b + 1]), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_paged_attention_block_scatter_invariance():
    """The output depends only on the table's *order*, not on where the
    blocks physically live in the pool: permuting pool rows (and the
    table with them) leaves the result unchanged."""
    q, kp, vp, bt, ln = _paged_inputs(2, 4, 2, 16, 10, 8, 4, seed=4)
    out = paged_attention(q, kp, vp, bt, ln)
    perm = np.random.default_rng(0).permutation(kp.shape[0])
    inv = np.argsort(perm)
    kp2 = jnp.asarray(np.asarray(kp)[perm])
    vp2 = jnp.asarray(np.asarray(vp)[perm])
    bt2 = jnp.where(bt >= 0, jnp.asarray(inv)[jnp.maximum(bt, 0)], -1)
    out2 = paged_attention(q, kp2, vp2, bt2, ln)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2),
                               atol=2e-6, rtol=2e-6)


# ---------------------------------------------------------------------------
# paged attention, multi-query (speculative verify q-block)
# ---------------------------------------------------------------------------

def _paged_mq_inputs(B, H, Hkv, hd, P, bs, NB, K, seed=0, dtype=jnp.float32):
    """Pool/table fixtures plus a (B, K, H, hd) q-block; lengths clamped
    so every query position ``lengths[b] - K + j`` is a real token."""
    _, kp, vp, bt, ln = _paged_inputs(B, H, Hkv, hd, P, bs, NB, seed=seed,
                                      dtype=dtype)
    q = jax.random.normal(jax.random.PRNGKey(seed + 100),
                          (B, K, H, hd)).astype(dtype)
    return q, kp, vp, bt, jnp.maximum(ln, K)


@pytest.mark.parametrize("K", [1, 2, 3, 4])
@pytest.mark.parametrize("B,H,Hkv,hd,P,bs,NB", [
    (2, 4, 4, 16, 10, 8, 4),
    (3, 4, 2, 32, 16, 16, 4),            # GQA
    (2, 8, 1, 64, 12, 8, 4),             # MQA
])
def test_paged_attention_mq_matches_ref(B, H, Hkv, hd, P, bs, NB, K):
    q, kp, vp, bt, ln = _paged_mq_inputs(B, H, Hkv, hd, P, bs, NB, K)
    out = paged_attention(q, kp, vp, bt, ln)
    assert out.shape == (B, K, H, hd)
    ref = paged_attention_ref(q, kp, vp, bt, ln)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_mq_k1_bit_identical_to_single(dtype):
    """A (B, H, hd) single query and the same query as a (B, 1, H, hd)
    q-block must agree BIT-EXACTLY — both run the one q-block kernel at
    K=1, so a speculative engine at k=1 prices and computes like the
    plain one."""
    q, kp, vp, bt, ln = _paged_inputs(3, 4, 2, 32, 16, 16, 4, seed=5,
                                      dtype=dtype)
    single = paged_attention(q, kp, vp, bt, ln)
    mq = paged_attention(q[:, None], kp, vp, bt, ln)
    assert single.shape == q.shape and mq.shape == (3, 1, 4, 32)
    np.testing.assert_array_equal(np.asarray(mq[:, 0], np.float32),
                                  np.asarray(single, np.float32))


def test_paged_attention_mq_identity_table_matches_flash():
    """Identity table + q-block == dense causal attention over the last K
    positions (flash oracle with q_offset = L - K)."""
    B, H, Hkv, hd, bs, NB, K = 2, 4, 2, 32, 8, 4, 3
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    Sk = NB * bs
    ln = jnp.array([Sk, Sk - 5], jnp.int32)
    kp = jax.random.normal(ks[1], (NB * B, bs, Hkv, hd))
    vp = jax.random.normal(ks[2], (NB * B, bs, Hkv, hd))
    q = jax.random.normal(ks[0], (B, K, H, hd))
    bt = jnp.arange(B * NB, dtype=jnp.int32).reshape(B, NB)
    out = paged_attention(q, kp, vp, bt, ln)
    kd = kp.reshape(B, Sk, Hkv, hd).transpose(0, 2, 1, 3)
    vd = vp.reshape(B, Sk, Hkv, hd).transpose(0, 2, 1, 3)
    for b in range(B):
        L_b = int(ln[b])
        ref = flash_attention_ref(
            q[b:b + 1].transpose(0, 2, 1, 3), kd[b:b + 1, :, :L_b],
            vd[b:b + 1, :, :L_b], causal=True,
            q_offset=L_b - K).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(out[b:b + 1]), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_paged_attention_mq_block_scatter_invariance():
    """q-block output depends only on table order, not pool placement."""
    q, kp, vp, bt, ln = _paged_mq_inputs(2, 4, 2, 16, 10, 8, 4, 3, seed=7)
    out = paged_attention(q, kp, vp, bt, ln)
    perm = np.random.default_rng(1).permutation(kp.shape[0])
    inv = np.argsort(perm)
    kp2 = jnp.asarray(np.asarray(kp)[perm])
    vp2 = jnp.asarray(np.asarray(vp)[perm])
    bt2 = jnp.where(bt >= 0, jnp.asarray(inv)[jnp.maximum(bt, 0)], -1)
    out2 = paged_attention(q, kp2, vp2, bt2, ln)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2),
                               atol=2e-6, rtol=2e-6)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,S,p,n,chunk", [
    (1, 2, 64, 16, 8, 16),
    (2, 4, 128, 32, 16, 32),
    (1, 1, 96, 8, 4, 8),
    (2, 2, 64, 16, 8, 64),    # single chunk
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_matches_sequential_ref(B, H, S, p, n, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (B, H, S, p)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, H, S))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, n)) * 0.5
    Cm = jax.random.normal(jax.random.PRNGKey(9), (B, S, n)) * 0.5
    out = ssd_scan(x, dt.astype(jnp.float32), A, Bm, Cm, chunk=chunk)
    ref = ssd_scan_ref(x, dt.astype(jnp.float32), A, Bm, Cm)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_ssd_scan_matches_model_chunked():
    """Kernel vs the model's jnp chunked SSD (both against the same math)."""
    from repro.models.mamba import ssd_chunked
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    B, H, S, p, n = 2, 3, 64, 16, 8
    x = jax.random.normal(ks[0], (B, H, S, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, H, S))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, n)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, n)) * 0.5
    kern = ssd_scan(x, dt, A, Bm, Cm, chunk=16)
    # model layout: x (b,s,h,p), dt (b,s,h)
    y_model, _ = ssd_chunked(x.transpose(0, 2, 1, 3),
                             dt.transpose(0, 2, 1), A, Bm, Cm, chunk=16)
    np.testing.assert_allclose(np.asarray(kern),
                               np.asarray(y_model.transpose(0, 2, 1, 3)),
                               atol=1e-4, rtol=1e-4)


def test_ssd_scan_state_threading_resumes_bit_exact():
    """Splitting a sequence across two kernel calls and threading the
    final state into the second call reproduces the single-call outputs
    BIT-EXACTLY (same chunk grid on both sides — the state-threaded
    chunked-prefill contract, DESIGN.md §13)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    B, H, S, p, n, chunk = 2, 3, 64, 16, 8, 16
    half = S // 2
    x = jax.random.normal(ks[0], (B, H, S, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, H, S))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, n)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, n)) * 0.5

    y_full, s_full = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk,
                              return_state=True)
    y1, s1 = ssd_scan(x[:, :, :half], dt[:, :, :half], A, Bm[:, :half],
                      Cm[:, :half], chunk=chunk, return_state=True)
    y2, s2 = ssd_scan(x[:, :, half:], dt[:, :, half:], A, Bm[:, half:],
                      Cm[:, half:], s1, chunk=chunk, return_state=True)
    np.testing.assert_array_equal(np.asarray(y_full[:, :, :half]),
                                  np.asarray(y1))
    np.testing.assert_array_equal(np.asarray(y_full[:, :, half:]),
                                  np.asarray(y2))
    np.testing.assert_array_equal(np.asarray(s_full), np.asarray(s2))


def test_ssd_scan_state_threading_matches_ref():
    """Kernel carried state agrees with the sequential-recurrence oracle's
    (same initial_state/return_state contract on both)."""
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    B, H, S, p, n = 1, 2, 32, 8, 4
    x = jax.random.normal(ks[0], (B, H, S, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, H, S))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, n)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, n)) * 0.5
    s0 = jax.random.normal(ks[5], (B, H, p, n)) * 0.2

    y_k, f_k = ssd_scan(x, dt, A, Bm, Cm, s0, chunk=8, return_state=True)
    y_r, f_r = ssd_scan_ref(x, dt, A, Bm, Cm, s0, return_state=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(f_k), np.asarray(f_r),
                               atol=1e-4, rtol=1e-4)
