"""Mamba2 (SSD — state-space duality) block, pure JAX.

Implements the chunked SSD algorithm of arXiv:2405.21060: intra-chunk
quadratic (attention-like) term + inter-chunk linear state recurrence via
``lax.scan``, O(S · chunk) memory. ngroups is fixed to 1 (all assigned
configs). ``repro.kernels.ssd_scan`` holds the Pallas TPU version of the
chunk kernel; this file is the oracle and the backend-portable path.

Decode maintains O(1) state: (conv_state (B, k-1, conv_dim),
ssm_state (B, H, P, N)).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.layers import dense_init


def init_ssm(cfg, key, dtype=jnp.float32):
    d, di, n, h = cfg.d_model, cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_heads
    conv_dim = di + 2 * n  # x, B, C are conv'd together (mamba2 convention)
    ks = jax.random.split(key, 5)
    # dt bias init so softplus(dt_bias) spans [1e-3, 1e-1] (mamba default)
    u = jax.random.uniform(ks[3], (h,), jnp.float32)
    dt = jnp.exp(u * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))  # inverse softplus
    return {
        "in_proj": dense_init(ks[0], (d, 2 * di + 2 * n + h), d, dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_conv, conv_dim),
                                     jnp.float32) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": dt_bias.astype(jnp.float32),
        "gate_norm": jnp.ones((di,), dtype),
        "out_proj": dense_init(ks[4], (di, d), di, dtype),
    }


def _segsum(x):
    """x (..., L) -> (..., L, L): S[i,j] = sum_{k=j+1..i} x[k], -inf above diag."""
    L = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((L, L), bool))
    return jnp.where(mask, seg, -jnp.inf)


def ssd_chunked(xh, dt, A, Bm, Cm, chunk: int, initial_state=None):
    """Chunked SSD scan.

    xh (b,s,h,p): per-head inputs (already multiplied by nothing; dt applied
    here); dt (b,s,h) — positive rates; A (h,) — negative decay;
    Bm, Cm (b,s,n) — shared across heads (ngroups=1).
    Returns (y (b,s,h,p), final_state (b,h,p,n)).
    """
    b, s, h, p = xh.shape
    n = Bm.shape[-1]
    pad = (-s) % chunk
    if pad:
        # identity-pad ragged sequences: dt=0 makes the padded steps exact
        # no-ops on the state (decay exp(0)=1, contribution dt·x·B=0)
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
        s_out = s
        s = s + pad
    else:
        s_out = s
    c = s // chunk

    xd = (xh * dt[..., None]).reshape(b, c, chunk, h, p)
    dA = (dt * A).reshape(b, c, chunk, h).transpose(0, 3, 1, 2)  # (b,h,c,l)
    Bc = Bm.reshape(b, c, chunk, n)
    Cc = Cm.reshape(b, c, chunk, n)

    dA_cum = jnp.cumsum(dA, axis=-1)                             # (b,h,c,l)
    # 1) intra-chunk (quadratic within chunk)
    L = jnp.exp(_segsum(dA))                                     # (b,h,c,l,l)
    y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp", Cc, Bc, L, xd)
    # 2) chunk-local states (contribution of each chunk to the running state)
    decay_states = jnp.exp(dA_cum[..., -1:] - dA_cum)            # (b,h,c,l)
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", Bc, decay_states, xd)
    # 3) inter-chunk recurrence
    chunk_decay = jnp.exp(dA_cum[..., -1])                       # (b,h,c)

    def step(st, inp):
        s_c, dec = inp                                           # (b,h,p,n),(b,h)
        new = st * dec[..., None, None] + s_c
        return new, st                                           # emit PREVIOUS

    init = (jnp.zeros((b, h, p, n), xh.dtype) if initial_state is None
            else initial_state.astype(xh.dtype))
    final, prev_states = lax.scan(
        step, init,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(2, 0, 1)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)           # (b,c,h,p,n)
    # 4) state -> output within chunk
    state_decay = jnp.exp(dA_cum)                                # (b,h,c,l)
    y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", Cc, prev_states, state_decay)
    y = (y_diag + y_off).reshape(b, s, h, p)
    return y[:, :s_out], final


def _causal_conv(x, w, b):
    """Depthwise causal conv1d. x (B,S,C), w (k,C), b (C)."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    # sum_k w[k] * x[t-k+1+i] — small k (4): unrolled adds, XLA fuses
    out = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(k))
    return out + b


def ssm_apply(p, x, cfg, initial_state=None, return_state=False):
    """Full-sequence SSD block. x (B,S,d) -> (B,S,d)."""
    B, S, d = x.shape
    di, n, h, hp = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    cd = x.dtype

    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(cd))
    z, xbc, dt_raw = jnp.split(zxbcdt, [di, 2 * di + 2 * n], axis=-1)
    xbc = jax.nn.silu(_causal_conv(xbc, p["conv_w"].astype(cd),
                                   p["conv_b"].astype(cd)))
    xs, Bm, Cm = jnp.split(xbc, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"])                         # (B,S,h)
    A = -jnp.exp(p["A_log"])                                     # (h,)

    xh = xs.reshape(B, S, h, hp).astype(jnp.float32)
    # FIXED inner chunk, never shrunk to S: exp(a)·exp(b) != exp(a+b)
    # bitwise, so the chunked scan only composes exactly across engine
    # chunk boundaries when the inner ssd chunk grid is anchored at
    # position 0 globally (ssd_chunked identity-pads ragged tails)
    y, final = ssd_chunked(xh, dt, A, Bm.astype(jnp.float32),
                           Cm.astype(jnp.float32), cfg.ssm_chunk,
                           initial_state=initial_state)
    y = y + xh * p["D"][None, None, :, None]
    y = y.reshape(B, S, di).astype(cd)
    # gated RMSNorm (mamba2)
    y = y * jax.nn.silu(z)
    yf = y.astype(jnp.float32)
    var = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    y = (yf * lax.rsqrt(var + 1e-5) * p["gate_norm"].astype(jnp.float32)
         ).astype(cd)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(cd))
    if return_state:
        k = cfg.ssm_conv
        # conv state: last k-1 pre-activation xbc inputs
        zxbc_raw = jnp.split(zxbcdt, [di, 2 * di + 2 * n], axis=-1)[1]
        conv_state = zxbc_raw[:, -(k - 1):, :] if S >= k - 1 else jnp.pad(
            zxbc_raw, ((0, 0), (k - 1 - S, 0), (0, 0)))
        return out, {"conv": conv_state.astype(cd), "ssm": final}
    return out


def ssm_apply_chunk(p, x, cfg, state, n_valid):
    """Chunk-resumed SSD block: one engine prefill chunk, equal to the
    matching slice of :func:`ssm_apply` over the whole prompt up to
    matmul rounding (a backend may accumulate the ``in_proj`` matmul of a
    C-row chunk in another order than that of the whole prompt, so the
    raw conv rows can differ by a few ulps).

    x (B, C, d) — the chunk's hidden states (tail rows may be padding);
    state — the carried-state pytree {conv (B, k-1, conv_dim) raw
    pre-activation xbc rows of the valid prefix, ssm (B, h, p, n)} from
    the previous chunk (all-zero at position 0 — identical to the
    monolithic left zero-pad / zero initial state); n_valid (B,) — valid
    rows in this chunk. Returns (out (B, C, d), new state).

    The scan composes exactly only when the caller splits prompts at
    multiples of ``cfg.ssm_chunk`` (the engine's ``chunk_multiple``): the
    inner ssd chunk grid then lands on the same global boundaries as the
    monolithic scan, so every decay product is the same float sequence.
    """
    B, C, d = x.shape
    di, n, h, hp = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    cd = x.dtype
    k = cfg.ssm_conv

    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(cd))
    z, xbc_raw, dt_raw = jnp.split(zxbcdt, [di, 2 * di + 2 * n], axis=-1)
    valid = (jnp.arange(C)[None, :] < n_valid[:, None])          # (B, C)

    # conv with the carried window as left context (zeros at position 0
    # == the monolithic zero pad; same unrolled-adds order as
    # _causal_conv so the first chunk is bit-identical)
    window = jnp.concatenate([state["conv"].astype(cd), xbc_raw], axis=1)
    w = p["conv_w"].astype(cd)
    xbc = sum(window[:, i:i + C, :] * w[i] for i in range(k))
    xbc = jax.nn.silu(xbc + p["conv_b"].astype(cd))
    xs, Bm, Cm = jnp.split(xbc, [di, di + n], axis=-1)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    # softplus is always > 0: padding rows must be masked explicitly so
    # they are exact no-ops on the state (decay exp(0)=1, contribution 0)
    dt = jnp.where(valid[..., None], dt, 0.0)                    # (B,C,h)
    A = -jnp.exp(p["A_log"])

    xh = xs.reshape(B, C, h, hp).astype(jnp.float32)
    y, final = ssd_chunked(xh, dt, A, Bm.astype(jnp.float32),
                           Cm.astype(jnp.float32), cfg.ssm_chunk,
                           initial_state=state["ssm"])
    y = y + xh * p["D"][None, None, :, None]
    y = y.reshape(B, C, di).astype(cd)
    y = y * jax.nn.silu(z)
    yf = y.astype(jnp.float32)
    var = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    y = (yf * lax.rsqrt(var + 1e-5) * p["gate_norm"].astype(jnp.float32)
         ).astype(cd)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(cd))

    # new conv state: the k-1 raw rows ending at the last VALID position
    # (window index n_valid-1 is absolute position pos0+n_valid-1); for
    # an all-padding row (n_valid == 0) this reproduces the old state
    idx = n_valid[:, None] + jnp.arange(k - 1)[None, :]          # (B, k-1)
    new_conv = jnp.take_along_axis(window, idx[..., None], axis=1)
    return out, {"conv": new_conv.astype(state["conv"].dtype), "ssm": final}


def ssm_decode_step(p, x1, state, cfg):
    """Single-token decode. x1 (B,1,d); state {conv (B,k-1,conv_dim),
    ssm (B,h,p,n)} -> (out (B,1,d), new state)."""
    B = x1.shape[0]
    di, n, h, hp = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    cd = x1.dtype
    k = cfg.ssm_conv

    zxbcdt = jnp.einsum("bsd,de->bse", x1, p["in_proj"].astype(cd))
    z, xbc_new, dt_raw = jnp.split(zxbcdt, [di, 2 * di + 2 * n], axis=-1)
    # roll conv window: state holds previous k-1 raw xbc rows
    window = jnp.concatenate([state["conv"], xbc_new], axis=1)   # (B,k,conv)
    w = p["conv_w"].astype(cd)
    xbc = sum(window[:, i, :] * w[i] for i in range(k)) + p["conv_b"].astype(cd)
    xbc = jax.nn.silu(xbc)[:, None, :]                           # (B,1,conv)
    xs, Bm, Cm = jnp.split(xbc, [di, di + n], axis=-1)

    dt = jax.nn.softplus(dt_raw[:, 0, :].astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt * A)                                         # (B,h)
    xh = xs[:, 0].reshape(B, h, hp).astype(jnp.float32)
    Bv = Bm[:, 0].astype(jnp.float32)                            # (B,n)
    Cv = Cm[:, 0].astype(jnp.float32)
    ssm = state["ssm"].astype(jnp.float32)
    ssm = (ssm * dA[..., None, None]
           + jnp.einsum("bhp,bn->bhpn", xh * dt[..., None], Bv))
    y = jnp.einsum("bhpn,bn->bhp", ssm, Cv) + xh * p["D"][None, :, None]
    y = y.reshape(B, 1, di).astype(cd)
    y = y * jax.nn.silu(z)
    yf = y.astype(jnp.float32)
    var = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    y = (yf * lax.rsqrt(var + 1e-5) * p["gate_norm"].astype(jnp.float32)
         ).astype(cd)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(cd))
    new_state = {"conv": window[:, 1:, :], "ssm": ssm.astype(state["ssm"].dtype)}
    return out, new_state


def init_ssm_state(cfg, batch: int, dtype):
    di, n = cfg.ssm_d_inner, cfg.ssm_state
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, di + 2 * n), dtype),
        "ssm": jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_head_dim, n),
                         jnp.float32),
    }
