"""Shared neural-net layers: norms, RoPE, attention (full + chunked
online-softmax), gated MLPs, chunked cross-entropy.

Pure JAX, params are plain dicts of arrays. All matmul-heavy ops accept a
``compute_dtype`` and cast weights/activations on entry; normalization and
softmax statistics are computed in float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
}

NEG_INF = -1e30  # large-negative instead of -inf: keeps softmax NaN-free when
                 # a row is fully masked (e.g. ring-buffer slots not yet valid)


def dtype_of(name: str):
    return DTYPES[name]


def constrain(x, sharding):
    """Apply an activation sharding constraint when one is configured.
    Without this XLA may shard remat-saved residual streams on the model
    axis (replicating the batch!) — observed 51GB/device on yi-9b."""
    if sharding is None:
        return x
    return lax.with_sharding_constraint(x, sharding)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_dim: int, dtype=jnp.float32):
    """Truncated-normal fan-in init (std = 1/sqrt(in_dim))."""
    std = in_dim ** -0.5
    return (jax.random.truncated_normal(key, -3.0, 3.0, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.truncated_normal(key, -3.0, 3.0, shape, jnp.float32)
            * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x, w, *, eps: float = 1e-5, unit_offset: bool = False):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + eps)
    scale = (1.0 + w.astype(jnp.float32)) if unit_offset else w.astype(jnp.float32)
    return (y * scale).astype(x.dtype)


def layernorm(x, w, b, *, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


@jax.named_scope("norm")
def apply_norm(x, p, cfg):
    if cfg.norm_type == "layernorm":
        return layernorm(x, p["w"], p["b"], eps=cfg.norm_eps)
    return rmsnorm(x, p["w"], eps=cfg.norm_eps,
                   unit_offset=cfg.rmsnorm_unit_offset)


def init_norm(cfg, dtype=jnp.float32):
    if cfg.norm_type == "layernorm":
        return {"w": jnp.ones((cfg.d_model,), dtype),
                "b": jnp.zeros((cfg.d_model,), dtype)}
    w = jnp.zeros if cfg.rmsnorm_unit_offset else jnp.ones
    return {"w": w((cfg.d_model,), dtype)}


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_cos_sin(positions, head_dim: int, theta: float):
    """positions: (...,) int -> cos/sin (..., head_dim//2) float32."""
    half = head_dim // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (B, S, H, hd); cos/sin: (S, hd/2) or (B, S, hd/2). Half-split style."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:   # (S, half) -> broadcast over batch and heads
        c = cos[None, :, None, :]
        s = sin[None, :, None, :]
    else:               # (B, S, half)
        c = cos[:, :, None, :]
        s = sin[:, :, None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    o1 = xf1 * c - xf2 * s
    o2 = xf2 * c + xf1 * s
    return jnp.concatenate([o1, o2], axis=-1).astype(x.dtype)


def sinusoidal_pos(seq: int, d_model: int):
    """Whisper-style sinusoid table (seq, d_model), float32."""
    half = d_model // 2
    freqs = jnp.exp(-math.log(10_000.0) * jnp.arange(half, dtype=jnp.float32)
                    / max(half - 1, 1))
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def init_attention(cfg, key, dtype=jnp.float32):
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h, hd), d, dtype),
        "wk": dense_init(ks[1], (d, hkv, hd), d, dtype),
        "wv": dense_init(ks[2], (d, hkv, hd), d, dtype),
        "wo": dense_init(ks[3], (h, hd, d), h * hd, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), dtype)
        p["bk"] = jnp.zeros((hkv, hd), dtype)
        p["bv"] = jnp.zeros((hkv, hd), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _qk_head_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def project_qkv(p, x, cfg, positions, *, x_kv=None, kv_positions=None,
                use_rope=True):
    """Project to q (B,S,H,hd) and k,v (B,T,Hkv,hd), with rope + qk-norm.

    ``x_kv`` enables cross-attention (keys/values from another sequence).
    """
    x_kv = x if x_kv is None else x_kv
    kv_positions = positions if kv_positions is None else kv_positions
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("btd,dhk->bthk", x_kv, p["wk"].astype(x.dtype))
    v = jnp.einsum("btd,dhk->bthk", x_kv, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if cfg.qk_norm:
        q = _qk_head_norm(q, p["q_norm"], cfg.norm_eps)
        k = _qk_head_norm(k, p["k_norm"], cfg.norm_eps)
    if use_rope and cfg.pos_embed == "rope":
        cos_q, sin_q = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
        cos_k, sin_k = rope_cos_sin(kv_positions, cfg.head_dim, cfg.rope_theta)
        q = apply_rope(q, cos_q, sin_q)
        k = apply_rope(k, cos_k, sin_k)
    return q, k, v


def repeat_kv(k, num_heads: int):
    """(B,T,Hkv,hd) -> (B,T,H,hd) by repeating each kv head H/Hkv times."""
    hkv = k.shape[2]
    if hkv == num_heads:
        return k
    return jnp.repeat(k, num_heads // hkv, axis=2)


PAD_POS = 2 ** 30   # sentinel position for padded kv slots


def _mask_bias(q_pos, k_pos, *, causal: bool, window):
    """Additive mask bias (..., Sq, Sk) from absolute positions.

    ``window``: 0 / None = unlimited; may be a traced scalar (per-layer
    dynamic window, e.g. hymba global-vs-SWA layers). Sentinel positions
    (>= PAD_POS/2) are always masked — chunk padding must not leak into
    non-causal attention (hypothesis-found edge case).
    """
    dq = q_pos[..., :, None]
    dk = k_pos[..., None, :]
    ok = dk < PAD_POS // 2
    ok &= jnp.ones(jnp.broadcast_shapes(dq.shape, dk.shape), bool)
    if causal:
        ok &= dk <= dq
    if window is not None:
        w = jnp.asarray(window)
        ok &= jnp.where(w > 0, dk > dq - w, True)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def full_attention(q, k, v, *, q_pos, k_pos, causal=True, window=None,
                   softcap: float = 0.0, extra_mask=None):
    """Dense attention. q (B,S,H,hd), k/v (B,T,H,hd) (kv already repeated).

    ``extra_mask``: optional (B, T) validity mask for cache slots.
    """
    hd = q.shape[-1]
    scores = jnp.einsum("bshk,bthk->bhst", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    if softcap > 0:
        scores = softcap * jnp.tanh(scores / softcap)
    bias = _mask_bias(q_pos, k_pos, causal=causal, window=window)
    scores = scores + bias  # (B,H,S,T) + (S,T) or (B,1?,S,T)
    if extra_mask is not None:
        scores = scores + jnp.where(extra_mask, 0.0, NEG_INF)[:, None, None, :]
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthk->bshk", probs, v)


def chunked_attention(q, k, v, *, q_pos, k_pos, causal=True, window=None,
                      softcap: float = 0.0, chunk_q: int = 512,
                      chunk_k: int = 512):
    """Flash-style online-softmax attention via lax.scan over q and kv blocks.

    Never materializes the (S, T) score matrix; peak memory is
    O(chunk_q * chunk_k) per head. This is the backend-portable oracle path;
    ``repro.kernels.flash_attention`` is the Pallas TPU version.
    q: (B,S,H,hd); k,v: (B,T,H,hd); q_pos (S,), k_pos (T,) absolute positions.
    """
    B, S, H, hd = q.shape
    T = k.shape[1]
    cq = min(chunk_q, S)
    ck = min(chunk_k, T)
    nq, nk = -(-S // cq), -(-T // ck)
    pad_q, pad_k = nq * cq - S, nk * ck - T
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad_q), constant_values=-1)
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        # padded kv slots get the sentinel position: always masked
        k_pos = jnp.pad(k_pos, (0, pad_k), constant_values=PAD_POS)

    qb = q.reshape(B, nq, cq, H, hd).transpose(1, 0, 2, 3, 4)
    kb = k.reshape(B, nk, ck, H, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nk, ck, H, hd).transpose(1, 0, 2, 3, 4)
    qpb = q_pos.reshape(nq, cq)
    kpb = k_pos.reshape(nk, ck)
    scale = 1.0 / math.sqrt(hd)

    def q_block(carry, qin):
        qc, qp = qin   # (B,cq,H,hd), (cq,)

        def kv_block(state, kin):
            m, l, acc = state
            kc, vc, kp = kin
            s = jnp.einsum("bshk,bthk->bhst", qc, kc).astype(jnp.float32) * scale
            if softcap > 0:
                s = softcap * jnp.tanh(s / softcap)
            s = s + _mask_bias(qp, kp, causal=causal, window=window)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = (acc * corr[..., None]
                       + jnp.einsum("bhst,bthk->bhsk", p.astype(qc.dtype), vc
                                    ).astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, cq), jnp.float32)
        a0 = jnp.zeros((B, H, cq, hd), jnp.float32)
        # checkpoint each kv block: backward recomputes the (cq, ck) prob
        # tiles instead of saving them for every block pair (flash-bwd
        # memory behaviour; the saved state per step is O(cq·hd), not cq·ck)
        (m, l, acc), _ = lax.scan(jax.checkpoint(kv_block), (m0, l0, a0),
                                  (kb, vb, kpb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return carry, out.transpose(0, 2, 1, 3).astype(qc.dtype)  # (B,cq,H,hd)

    _, outs = lax.scan(q_block, (), (qb, qpb))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, nq * cq, H, hd)
    return out[:, :S]


def attn_output(p, ctx_heads, out_dtype):
    return jnp.einsum("bshk,hkd->bsd", ctx_heads,
                      p["wo"].astype(ctx_heads.dtype)).astype(out_dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(cfg, key, dtype=jnp.float32):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_act in ("swiglu", "geglu"):
        return {"w_gate": dense_init(ks[0], (d, f), d, dtype),
                "w_up": dense_init(ks[1], (d, f), d, dtype),
                "w_down": dense_init(ks[2], (f, d), f, dtype)}
    return {"w_up": dense_init(ks[0], (d, f), d, dtype),
            "w_down": dense_init(ks[1], (f, d), f, dtype)}


@jax.named_scope("mlp")
def mlp_apply(p, x, cfg):
    if cfg.mlp_act in ("swiglu", "geglu"):
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype))
        u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype))
        act = jax.nn.silu if cfg.mlp_act == "swiglu" else jax.nn.gelu
        h = act(g) * u
    else:
        h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x,
                                   p["w_up"].astype(x.dtype)))
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(x.dtype))


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def chunked_cross_entropy(hidden, w_out, labels, *, valid, vocab_size: int,
                          chunk: int = 512):
    """Cross-entropy without materializing full (B,S,V) logits.

    hidden (B,S,d), w_out (d,Vp), labels (B,S) int32, valid (B,S) bool.
    Logits are computed per sequence-chunk inside a scan; statistics in f32.
    Padded vocab entries (>= vocab_size) are masked out. Returns
    (sum_loss, sum_valid) so callers control normalization.
    """
    B, S, d = hidden.shape
    Vp = w_out.shape[1]
    c = min(chunk, S)
    n = -(-S // c)
    pad = n * c - S
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        valid = jnp.pad(valid, ((0, 0), (0, pad)))
    hb = hidden.reshape(B, n, c, d).transpose(1, 0, 2, 3)
    lb = labels.reshape(B, n, c).transpose(1, 0, 2)
    vb = valid.reshape(B, n, c).transpose(1, 0, 2)
    vocab_ok = (jnp.arange(Vp) < vocab_size)

    vocab_ids = jnp.arange(Vp)

    def body(carry, xs):
        loss_sum, n_valid = carry
        h, lbl, ok = xs
        logits = jnp.einsum("bsd,dv->bsv", h, w_out.astype(h.dtype)
                            ).astype(jnp.float32)
        logits = jnp.where(vocab_ok, logits, NEG_INF)
        # vocab-parallel-safe lse and gold: only elementwise ops + reductions
        # over the (possibly model-sharded) vocab dim — XLA reduces locally
        # then inserts small (B,c)-sized all-reduces. (take_along_axis here
        # partitions catastrophically: full-logit gathers.)
        mx = jnp.max(logits, axis=-1)
        lse = mx + jnp.log(jnp.sum(jnp.exp(logits - mx[..., None]), axis=-1))
        gold_mask = vocab_ids[None, None, :] == lbl[..., None]
        gold = jnp.sum(jnp.where(gold_mask, logits, 0.0), axis=-1)
        nll = (lse - gold) * ok.astype(jnp.float32)
        return (loss_sum + jnp.sum(nll),
                n_valid + jnp.sum(ok.astype(jnp.float32))), None

    (loss_sum, n_valid), _ = lax.scan(
        jax.checkpoint(body),
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hb, lb, vb))
    return loss_sum, n_valid
