"""Decoder-only transformer LM: dense / MoE / SSM / hybrid blocks.

One stacked-parameter block structure per model so layers run under
``lax.scan`` (small HLO, fast compile at 80 layers). Heterogeneity across
layers (hymba global-vs-sliding-window attention) is carried as a stacked
per-layer flag consumed inside the scanned body.

Three entry points per model:
  * ``train_loss(params, batch)``    — full causal forward + chunked CE
  * ``prefill(params, batch)``       — forward + build KV/SSM cache
  * ``decode_step(params, cache, token, pos)`` — one-token serve step
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from repro.config import (BLOCK_DENSE, BLOCK_HYBRID, BLOCK_MOE, BLOCK_SSM,
                          ModelConfig)
from repro.kernels.paged_attention import paged_attention
from repro.kernels.paged_attention.paged_attention import pages_in_place
from repro.models import layers as L
from repro.models import mamba, moe


# ---------------------------------------------------------------------------
# KV-cache head layout
# ---------------------------------------------------------------------------

def kv_store_heads(cfg: ModelConfig, tp: int) -> int:
    """Number of kv heads to *store* in the cache: the smallest replication
    of the true kv heads that the model mesh axis divides (Megatron-style
    kv-head replication for TP > kv_heads). Falls back to no replication
    (cache replicated across TP) when head counts are coprime to tp."""
    if cfg.num_kv_heads == 0:
        return 0
    reps = cfg.num_heads // cfg.num_kv_heads
    for r in range(1, reps + 1):
        if reps % r == 0 and (cfg.num_kv_heads * r) % tp == 0 \
                and cfg.num_heads % (cfg.num_kv_heads * r) == 0:
            return cfg.num_kv_heads * r
    return cfg.num_kv_heads


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_block(cfg: ModelConfig, key, dtype):
    ks = jax.random.split(key, 8)
    p: Dict[str, Any] = {"ln1": L.init_norm(cfg, dtype)}
    if cfg.uses_attention:
        p["attn"] = L.init_attention(cfg, ks[0], dtype)
    if cfg.block == BLOCK_HYBRID:
        p["ssm"] = mamba.init_ssm(cfg, ks[1], dtype)
        # per-branch output norms before averaging (hymba)
        p["attn_out_norm"] = jnp.ones((cfg.d_model,), dtype)
        p["ssm_out_norm"] = jnp.ones((cfg.d_model,), dtype)
    if cfg.block == BLOCK_SSM:
        p["ssm"] = mamba.init_ssm(cfg, ks[1], dtype)
    if cfg.block in (BLOCK_DENSE, BLOCK_HYBRID):
        p["ln2"] = L.init_norm(cfg, dtype)
        p["mlp"] = L.init_mlp(cfg, ks[2], dtype)
    if cfg.block == BLOCK_MOE:
        p["ln2"] = L.init_norm(cfg, dtype)
        p["moe"] = moe.init_moe(cfg, ks[2], dtype)
    return p


def init_lm_params(cfg: ModelConfig, key, dtype):
    ks = jax.random.split(key, 3)
    params: Dict[str, Any] = {
        "embed": L.embed_init(ks[0], (cfg.padded_vocab, cfg.d_model), dtype),
        "final_norm": L.init_norm(cfg, dtype),
    }
    layer_keys = jax.random.split(ks[1], cfg.num_layers)
    params["blocks"] = jax.vmap(
        lambda k: init_block(cfg, k, dtype))(layer_keys)
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(
            ks[2], (cfg.d_model, cfg.padded_vocab), cfg.d_model, dtype)
    return params


def layer_flags(cfg: ModelConfig):
    """Stacked per-layer metadata: is_global (full attention) flag."""
    flags = jnp.zeros((cfg.num_layers,), bool)
    for i in cfg.global_layers:
        flags = flags.at[i].set(True)
    return flags


# ---------------------------------------------------------------------------
# Block forward (full sequence: train / prefill)
# ---------------------------------------------------------------------------

def _attn_branch(cfg, p, xn, positions, is_global, knobs,
                 collect_cache: bool, cache_heads: int):
    """Self-attention on normed input. Returns (out, cache_or_None)."""
    p = p["attn"]
    S = xn.shape[1]
    q, k, v = L.project_qkv(p, xn, cfg, positions)
    # dynamic per-layer window: 0 disables the window clause in the mask
    if cfg.swa_window > 0:
        window = jnp.where(is_global, 0, cfg.swa_window)
    else:
        window = None
    kf = L.repeat_kv(k, cfg.num_heads)
    vf = L.repeat_kv(v, cfg.num_heads)
    # pin head-sharded attention when heads divide the model axis —
    # otherwise XLA may pick context-parallel attention whose bwd carries
    # save with UNSHARDED heads (2.15GB/layer on internvl; §Perf)
    attn_sh = knobs.get("attn_sharding")
    if attn_sh is not None:
        q = L.constrain(q, attn_sh)
        kf = L.constrain(kf, attn_sh)
        vf = L.constrain(vf, attn_sh)
    if S > knobs["attn_chunk_threshold"]:
        ctx = L.chunked_attention(
            q, kf, vf, q_pos=positions, k_pos=positions, causal=True,
            window=window, softcap=cfg.logit_softcap,
            chunk_q=knobs["attn_chunk"],
            chunk_k=knobs.get("attn_chunk_kv") or knobs["attn_chunk"])
    else:
        ctx = L.full_attention(q, kf, vf, q_pos=positions, k_pos=positions,
                               causal=True, window=window,
                               softcap=cfg.logit_softcap)
    out = L.attn_output(p, ctx, xn.dtype)
    cache = None
    if collect_cache:
        kc = L.repeat_kv(k, cache_heads)
        vc = L.repeat_kv(v, cache_heads)
        cache = {"k": kc, "v": vc}
    return out, cache


def block_forward(cfg, p, x, positions, is_global, knobs, *,
                  collect_cache=False, cache_heads=0, collect_state=False,
                  dropless_moe=False):
    """One block, full-sequence. Returns (x, aux, cache).

    ``dropless_moe`` selects the serve-time per-token routing
    (:func:`moe.moe_apply_dropless`) — parity-safe under any chunking —
    over training's capacity-bounded grouped routing."""
    aux: Dict[str, Any] = {}
    cache: Dict[str, Any] = {}
    xn = L.apply_norm(x, p["ln1"], cfg)

    if cfg.block == BLOCK_SSM:
        if collect_state:
            out, st = mamba.ssm_apply(p["ssm"], xn, cfg, return_state=True)
            cache.update(st)
        else:
            out = mamba.ssm_apply(p["ssm"], xn, cfg)
        x = x + out
    elif cfg.block == BLOCK_HYBRID:
        a_out, a_cache = _attn_branch(cfg, p, xn, positions, is_global, knobs,
                                      collect_cache, cache_heads)
        if collect_state:
            s_out, st = mamba.ssm_apply(p["ssm"], xn, cfg, return_state=True)
            cache.update(st)
        else:
            s_out = mamba.ssm_apply(p["ssm"], xn, cfg)
        a_out = L.rmsnorm(a_out, p["attn_out_norm"], eps=cfg.norm_eps)
        s_out = L.rmsnorm(s_out, p["ssm_out_norm"], eps=cfg.norm_eps)
        x = x + 0.5 * (a_out + s_out)
        if a_cache:
            cache.update(a_cache)
    else:  # dense / moe attention sublayer
        a_out, a_cache = _attn_branch(cfg, p, xn, positions, is_global, knobs,
                                      collect_cache, cache_heads)
        x = x + a_out
        if a_cache:
            cache.update(a_cache)

    if cfg.block in (BLOCK_DENSE, BLOCK_HYBRID):
        x = x + L.mlp_apply(p["mlp"], L.apply_norm(x, p["ln2"], cfg), cfg)
    elif cfg.block == BLOCK_MOE:
        moe_fn = moe.moe_apply_dropless if dropless_moe else moe.moe_apply
        m_out, m_aux = moe_fn(p["moe"], L.apply_norm(x, p["ln2"], cfg), cfg)
        x = x + m_out
        aux.update(m_aux)
    return x, aux, cache


# ---------------------------------------------------------------------------
# Backbone
# ---------------------------------------------------------------------------

@jax.named_scope("embed")
def embed_tokens(cfg, params, tokens, compute_dtype):
    x = jnp.take(params["embed"], tokens, axis=0).astype(compute_dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), compute_dtype)
    return x


def backbone(cfg, params, x, positions, knobs, *, collect_cache=False,
             cache_heads=0, collect_state=False, remat=True,
             dropless_moe=False):
    """Scan blocks over stacked params. x (B,S,d) -> (hidden, aux, caches)."""
    flags = layer_flags(cfg)

    def body(h, xs):
        p_l, flag = xs
        h = L.constrain(h, knobs.get("act_sharding"))
        h, aux, cache = block_forward(
            cfg, p_l, h, positions, flag, knobs,
            collect_cache=collect_cache, cache_heads=cache_heads,
            collect_state=collect_state, dropless_moe=dropless_moe)
        h = L.constrain(h, knobs.get("act_sharding"))
        return h, (aux, cache)

    if remat:
        body = jax.checkpoint(body)
    x, (auxs, caches) = lax.scan(body, x, (params["blocks"], flags))
    aux = {k: jnp.mean(v) for k, v in auxs.items()}
    x = L.apply_norm(x, params["final_norm"], cfg)
    return x, aux, caches


def lm_head_weight(cfg, params):
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


@jax.named_scope("lm_head")
def _paged_logits(cfg, params, hidden, compute_dtype):
    """float32 logits of ``hidden`` (..., d) over the padded vocabulary,
    padding entries masked: the tail of every paged program."""
    w_out = lm_head_weight(cfg, params).astype(compute_dtype)
    logits = (hidden @ w_out).astype(jnp.float32)
    vocab_ok = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
    return jnp.where(vocab_ok, logits, L.NEG_INF)


def _layer_slice(tree, idx):
    """One layer's slice of a stacked (L, ...) cache pytree — shared by
    every cache-carrying scan (decode, chunked prefill, paged paths)."""
    return jax.tree_util.tree_map(
        lambda c: lax.dynamic_index_in_dim(c, idx, 0, keepdims=False), tree)


def _layer_put(tree, new, idx):
    """Write one layer's updated entries back into the stacked cache
    (in-place under XLA's while-loop aliasing; see make_decode_step)."""
    return jax.tree_util.tree_map(
        lambda c, n: lax.dynamic_update_index_in_dim(
            c, n.astype(c.dtype), idx, 0), tree, new)


# ---------------------------------------------------------------------------
# Public entry points (decoder-only; enc-dec lives in encdec.py)
# ---------------------------------------------------------------------------

def make_train_loss(cfg: ModelConfig, knobs):
    compute_dtype = L.dtype_of(knobs["compute_dtype"])

    def train_loss(params, batch):
        tokens = batch["tokens"]
        x = embed_tokens(cfg, params, tokens, compute_dtype)
        positions = jnp.arange(x.shape[1])
        if cfg.frontend == "patch_stub":
            # prepend precomputed patch embeddings (frontend stub)
            pe = batch["patch_embeds"].astype(compute_dtype)
            x = jnp.concatenate([pe, x], axis=1)
            positions = jnp.arange(x.shape[1])
        hidden, aux, _ = backbone(cfg, params, x, positions, knobs,
                                  remat=knobs["remat"])
        labels = batch["labels"]
        if cfg.frontend == "patch_stub":
            # keep the full (nicely sharded) sequence; mask the patch
            # positions in the loss instead of slicing hidden — slicing
            # makes the text length ragged vs the SP shards / CE chunks and
            # XLA replicates the whole stream (+12GB on internvl, §Perf)
            F = batch["patch_embeds"].shape[1]
            pad = jnp.full((labels.shape[0], F), -1, labels.dtype)
            labels = jnp.concatenate([pad, labels], axis=1)
        valid = labels >= 0
        loss_sum, n_valid = L.chunked_cross_entropy(
            hidden, lm_head_weight(cfg, params).astype(compute_dtype),
            jnp.maximum(labels, 0), valid=valid, vocab_size=cfg.vocab_size,
            chunk=knobs["loss_chunk"])
        loss = loss_sum / jnp.maximum(n_valid, 1.0)
        if "moe_lb_loss" in aux:
            loss = loss + 0.01 * aux["moe_lb_loss"] + 1e-3 * aux["moe_z_loss"]
        metrics = {"loss": loss, **aux}
        return loss, metrics

    return train_loss


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, tp: int,
               compute_dtype):
    """Stacked (L, ...) cache pytree. ``cache_len`` already reflects
    ring-buffer windowing when enabled."""
    Lc = cfg.num_layers
    c: Dict[str, Any] = {}
    if cfg.uses_attention:
        gs = kv_store_heads(cfg, tp)
        c["k"] = jnp.zeros((Lc, batch, cache_len, gs, cfg.head_dim),
                           compute_dtype)
        c["v"] = jnp.zeros((Lc, batch, cache_len, gs, cfg.head_dim),
                           compute_dtype)
        c["pos"] = jnp.full((Lc, cache_len), -1, jnp.int32)
    if cfg.block in (BLOCK_SSM, BLOCK_HYBRID):
        di, n = cfg.ssm_d_inner, cfg.ssm_state
        c["conv"] = jnp.zeros((Lc, batch, cfg.ssm_conv - 1, di + 2 * n),
                              compute_dtype)
        c["ssm"] = jnp.zeros((Lc, batch, cfg.ssm_heads, cfg.ssm_head_dim, n),
                             jnp.float32)
    return c


def make_prefill(cfg: ModelConfig, knobs, tp: int):
    compute_dtype = L.dtype_of(knobs["compute_dtype"])
    cache_heads = kv_store_heads(cfg, tp)

    def prefill(params, batch, cache_len: int):
        """Run the prompt, return (last-position logits, cache)."""
        tokens = batch["tokens"]
        x = embed_tokens(cfg, params, tokens, compute_dtype)
        positions = jnp.arange(x.shape[1])
        if cfg.frontend == "patch_stub":
            pe = batch["patch_embeds"].astype(compute_dtype)
            x = jnp.concatenate([pe, x], axis=1)
            positions = jnp.arange(x.shape[1])
        S = x.shape[1]
        hidden, _, caches = backbone(
            cfg, params, x, positions, knobs, collect_cache=True,
            cache_heads=cache_heads, collect_state=True,
            remat=knobs["remat"], dropless_moe=True)
        # place collected kv into fixed-capacity cache buffers
        B = x.shape[0]
        cache = init_cache(cfg, B, cache_len, tp, compute_dtype)
        if cfg.uses_attention:
            W = cache_len
            if S <= W:
                cache["k"] = lax.dynamic_update_slice_in_dim(
                    cache["k"], caches["k"].astype(compute_dtype), 0, axis=2)
                cache["v"] = lax.dynamic_update_slice_in_dim(
                    cache["v"], caches["v"].astype(compute_dtype), 0, axis=2)
                pos_row = jnp.where(jnp.arange(W) < S, jnp.arange(W), -1)
            else:  # ring buffer: keep last W entries at rotated slots
                keep_k = caches["k"][:, :, S - W:]
                keep_v = caches["v"][:, :, S - W:]
                abs_pos = jnp.arange(S - W, S)
                slots = abs_pos % W
                order = jnp.argsort(slots)
                cache["k"] = keep_k[:, :, order].astype(compute_dtype)
                cache["v"] = keep_v[:, :, order].astype(compute_dtype)
                pos_row = abs_pos[order]
            cache["pos"] = jnp.broadcast_to(pos_row,
                                            (cfg.num_layers, cache_len))
        if cfg.block in (BLOCK_SSM, BLOCK_HYBRID):
            cache["conv"] = caches["conv"].astype(compute_dtype)
            cache["ssm"] = caches["ssm"]
        w_out = lm_head_weight(cfg, params).astype(compute_dtype)
        logits = (hidden[:, -1, :] @ w_out).astype(jnp.float32)
        vocab_ok = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        return jnp.where(vocab_ok, logits, L.NEG_INF), cache

    return prefill


def _masked_group_attention(cfg, q, keys, values, okay, out_dtype):
    """Shared grouped-attention core of the slot (ring) and paged
    (block-table) cached-attention paths: grouped scores, softcap,
    additive NEG_INF mask, softmax, context heads (B,C,H,hd), before the
    output projection. The two paths differ only in how keys/values/mask
    are produced — the math here MUST stay one copy or a softcap/masking
    fix could silently diverge them and break the token-parity guarantee
    CI asserts.

    q (B,C,H,hd); keys/values (B,T,Gs,hd); okay broadcastable to
    (B,C,T).
    """
    B, C = q.shape[0], q.shape[1]
    gs = keys.shape[2]
    R = cfg.num_heads // gs
    qg = q.reshape(B, C, gs, R, cfg.head_dim)
    s = jnp.einsum("bqgrk,btgk->bgrqt", qg, keys).astype(jnp.float32)
    s = s / math.sqrt(cfg.head_dim)
    if cfg.logit_softcap > 0:
        s = cfg.logit_softcap * jnp.tanh(s / cfg.logit_softcap)
    s = s + jnp.where(okay, 0.0, L.NEG_INF)[:, None, None, :, :]
    prob = jax.nn.softmax(s, axis=-1).astype(out_dtype)
    ctx = jnp.einsum("bgrqt,btgk->bqgrk", prob, values)
    return ctx.reshape(B, C, cfg.num_heads, cfg.head_dim)


def _cached_attn(cfg, p, xn, layer_cache, qpos, wslot, is_global):
    """Attention for query tokens against (and into) the cache — the
    shared core of single-token decode and chunked prefill.

    xn (B,C,d); layer_cache k/v (B,W,Gs,hd), pos (W,); ``qpos`` (C,) the
    queries' absolute positions, ``wslot`` (C,) the cache slot each query
    writes its k/v/pos to. The writes are drop-mode scatters: aiming a
    query at the out-of-range slot ``W`` (parked decode rows, chunk
    padding) writes *nothing*, which is what lets a continuous-batching
    engine run the decode vmap over its whole slot pool while some slots
    are free or still mid-chunked-prefill. Queries then attend over the
    whole updated cache, causally masked on the stored absolute
    positions — earlier chunks of the same prompt are just cache entries.
    """
    gs = layer_cache["k"].shape[2]
    q, k, v = L.project_qkv(p, xn, cfg, qpos)
    kc = L.repeat_kv(k, gs)
    vc = L.repeat_kv(v, gs)
    new_k = layer_cache["k"].at[:, wslot].set(kc, mode="drop")
    new_v = layer_cache["v"].at[:, wslot].set(vc, mode="drop")
    new_pos = layer_cache["pos"].at[wslot].set(
        qpos.astype(jnp.int32), mode="drop")

    kpos = new_pos  # (W,)
    okay = (kpos[None, :] >= 0) & (kpos[None, :] <= qpos[:, None])  # (C, W)
    if cfg.swa_window > 0:
        win_ok = kpos[None, :] > qpos[:, None] - cfg.swa_window
        okay = okay & jnp.where(is_global, True, win_ok)
    ctx = _masked_group_attention(cfg, q, new_k, new_v, okay[None], xn.dtype)
    return L.attn_output(p, ctx, xn.dtype), {"k": new_k, "v": new_v,
                                             "pos": new_pos}


def _decode_attn(cfg, p, xn, layer_cache, pos, is_global, tp):
    """One-token attention against the cache: the C=1 case of
    :func:`_cached_attn`. A negative (parked) ``pos`` writes nothing."""
    qpos = jnp.full((1,), pos)
    wslot = jnp.where(qpos >= 0, qpos % layer_cache["k"].shape[1],
                      layer_cache["k"].shape[1])
    return _cached_attn(cfg, p, xn, layer_cache, qpos, wslot, is_global)


def make_decode_step(cfg: ModelConfig, knobs, tp: int):
    compute_dtype = L.dtype_of(knobs["compute_dtype"])
    flags = layer_flags(cfg)

    def decode_step(params, cache, token, pos):
        """token (B,1) int32, pos scalar int32 -> (logits (B,Vp), cache).

        The cache rides in the scan CARRY and is updated in place per layer
        (dynamic_update_index on the stacked buffers): XLA's while-loop
        in-place analysis then aliases it end-to-end with the donated input
        — passing it as scan xs/ys instead costs 2 extra full-cache copies
        (observed +52GB on qwen3 decode_32k; EXPERIMENTS.md §Perf).
        """
        x = embed_tokens(cfg, params, token, compute_dtype)

        def body(carry, xs):
            h, cch = carry
            p_l, flag, idx = xs
            cache_l = _layer_slice(cch, idx)
            new_cache: Dict[str, Any] = {}
            xn = L.apply_norm(h, p_l["ln1"], cfg)

            def ssm_guarded(p_l, cache_l):
                # parked slots (pos < 0) must keep their carried state: a
                # slot mid-chunked-prefill is parked between chunk
                # deposits while live slots decode, and an unguarded
                # update would overwrite the partially-deposited scan
                # state with a garbage-token step (attention is naturally
                # guarded — its parked write slot drops out of range)
                state = {"conv": cache_l["conv"], "ssm": cache_l["ssm"]}
                out, st = mamba.ssm_decode_step(p_l["ssm"], xn, state, cfg)
                st = {k: jnp.where(pos >= 0, v.astype(state[k].dtype),
                                   state[k])
                      for k, v in st.items()}
                return out, st

            if cfg.block == BLOCK_SSM:
                out, st = ssm_guarded(p_l, cache_l)
                h = h + out
                new_cache.update(st)
            elif cfg.block == BLOCK_HYBRID:
                a_out, a_cache = _decode_attn(cfg, p_l["attn"], xn, cache_l,
                                              pos, flag, tp)
                s_out, st = ssm_guarded(p_l, cache_l)
                a_out = L.rmsnorm(a_out, p_l["attn_out_norm"], eps=cfg.norm_eps)
                s_out = L.rmsnorm(s_out, p_l["ssm_out_norm"], eps=cfg.norm_eps)
                h = h + 0.5 * (a_out + s_out)
                new_cache.update(a_cache)
                new_cache.update(st)
            else:
                a_out, a_cache = _decode_attn(cfg, p_l["attn"], xn, cache_l,
                                              pos, flag, tp)
                h = h + a_out
                new_cache.update(a_cache)
            if cfg.block in (BLOCK_DENSE, BLOCK_HYBRID):
                h = h + L.mlp_apply(p_l["mlp"],
                                    L.apply_norm(h, p_l["ln2"], cfg), cfg)
            elif cfg.block == BLOCK_MOE:
                m_out, _ = moe.moe_apply_dropless(
                    p_l["moe"], L.apply_norm(h, p_l["ln2"], cfg), cfg)
                h = h + m_out
            return (h, _layer_put(cch, new_cache, idx)), None

        (x, new_cache), _ = lax.scan(
            body, (x, cache),
            (params["blocks"], flags, jnp.arange(cfg.num_layers)))
        x = L.apply_norm(x, params["final_norm"], cfg)
        w_out = lm_head_weight(cfg, params).astype(compute_dtype)
        logits = (x[:, 0, :] @ w_out).astype(jnp.float32)
        vocab_ok = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        return jnp.where(vocab_ok, logits, L.NEG_INF), new_cache

    return decode_step


# ---------------------------------------------------------------------------
# Chunked prefill (fixed-shape prompt deposit for continuous serving)
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# Paged KV: block-table cache (DESIGN.md §9)
# ---------------------------------------------------------------------------

def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                     tp: int, compute_dtype, num_rows: int = 0):
    """Global KV block pool + per-row carried state.

    k/v are (L, P, bs, Gs, hd): one block table entry maps a request's
    token range [i*bs, (i+1)*bs) onto a pool block shared across all
    layers, so positions are structural — no per-token position array is
    stored (the slot cache needs one for its ring addressing; the paged
    cache does not). Recurrent carried state (SSM conv/ssm leaves) is NOT
    block-addressable — it is one fixed-size pytree per *request row* —
    so those leaves are (L, num_rows, ...), row-aligned with the engine's
    request rows and threaded through the chunk/decode steps explicitly
    (DESIGN.md §13)."""
    if cfg.frontend == "patch_stub":
        raise ValueError("paged KV does not support the patch_stub "
                         "modality frontend (prepended frontend tokens "
                         "have no block-table deposit path)")
    c: Dict[str, Any] = {}
    if cfg.uses_attention:
        gs = kv_store_heads(cfg, tp)
        shape = (cfg.num_layers, num_blocks, block_size, gs, cfg.head_dim)
        c["k"] = jnp.zeros(shape, compute_dtype)
        c["v"] = jnp.zeros(shape, compute_dtype)
    if cfg.block in (BLOCK_SSM, BLOCK_HYBRID):
        Lc, di, n = cfg.num_layers, cfg.ssm_d_inner, cfg.ssm_state
        c["conv"] = jnp.zeros((Lc, num_rows, cfg.ssm_conv - 1, di + 2 * n),
                              compute_dtype)
        c["ssm"] = jnp.zeros((Lc, num_rows, cfg.ssm_heads, cfg.ssm_head_dim,
                              n), jnp.float32)
    return c


def paged_decode_path(kv_heads: int, head_dim: int, platform: str) -> str:
    """The path single-token paged decode attention takes, lowered for
    ``platform`` over pool pages of ``kv_heads`` x ``head_dim``:
    ``"paged_kernel"`` (each row's live pages read in place,
    :mod:`repro.kernels.paged_attention`) or ``"gather"`` (every row's
    whole table gathered, :func:`_paged_gather_context`)."""
    if platform == "tpu" and pages_in_place(kv_heads, head_dim):
        return "paged_kernel"
    return "gather"


def _paged_gather_context(cfg, q, k_pool, v_pool, tables, qpos, window,
                          out_dtype):
    """Context heads (B,C,H,hd) of queries q (B,C,H,hd) at ``qpos``
    against their rows' pages, gathered at the table's full width: token
    t of row b at index t of a (B, NB*bs, Gs, hd) copy. ``window`` is None
    or an int32 scalar, traced or not (0 = none)."""
    B = q.shape[0]
    P, bs, gs, hd = k_pool.shape
    NB = tables.shape[1]
    flat = jnp.maximum(tables, 0).reshape(-1)
    kg = jnp.take(k_pool, flat, axis=0).reshape(B, NB * bs, gs, hd)
    vg = jnp.take(v_pool, flat, axis=0).reshape(B, NB * bs, gs, hd)

    kpos = jnp.arange(NB * bs)                        # structural positions
    okay = (kpos[None, None, :] <= qpos[:, :, None]) \
        & jnp.repeat(tables >= 0, bs, axis=1)[:, None, :]
    if window is not None:
        okay = okay & ((window <= 0)
                       | (kpos[None, None, :] > qpos[:, :, None] - window))
    return _masked_group_attention(cfg, q, kg, vg, okay, out_dtype)


def _paged_kernel_context(cfg, q, k_pool, v_pool, tables, qpos, wvalid,
                          window, *, interpret=False):
    """:func:`_paged_gather_context` for one query a row (C == 1),
    through the paged-attention kernel: each row reads its live pages in
    place, up to its length (``qpos + 1``; 0 for a row that writes
    nothing, which reads nothing and gets zeros)."""
    lengths = jnp.where(wvalid[:, 0], qpos[:, 0] + 1, 0)
    ctx = paged_attention(q[:, 0], k_pool, v_pool, tables, lengths,
                          window=0 if window is None else window,
                          softcap=cfg.logit_softcap, interpret=interpret)
    return ctx[:, None]


@jax.named_scope("attention")
def _paged_attn(cfg, p, xn, layer_cache, tables, qpos, wvalid, is_global):
    """Attention for query tokens against (and into) the paged pool — the
    block-table analogue of :func:`_cached_attn`, batched across requests.

    xn (B,C,d); layer_cache k/v (P,bs,Gs,hd) — ONE pool shared by every
    request; tables (B,NB) int32 block tables (-1 = absent entry); qpos
    (B,C) absolute query positions (per row — requests decode at
    different depths); wvalid (B,C) marks queries allowed to write their
    k/v (chunk padding and parked rows are not).

    Writes scatter each query's k/v into block ``tables[b, qpos//bs]`` at
    offset ``qpos % bs`` — parked/padded queries aim at the out-of-range
    block index ``P`` and the explicit ``mode="drop"`` discards them
    (default scatter semantics would wraparound-corrupt a live block).
    Queries then attend over their own pages, causally masked on the
    *structural* positions (table entry i holds tokens [i*bs, (i+1)*bs))
    — stale pages of a block's previous owner are never at a position
    <= qpos of the new owner, so block recycling needs no blanking
    dispatch. Single-token decode (C == 1) lowered for a TPU reads each
    row's live pages in place through the paged-attention kernel
    (:func:`paged_decode_path`); chunks, verify q-blocks and other
    platforms gather each row's whole table.
    """
    B, C = xn.shape[:2]
    P, bs, gs, hd = layer_cache["k"].shape
    NB = tables.shape[1]
    q, k, v = L.project_qkv(p, xn, cfg, qpos)        # per-row rope positions
    kc = L.repeat_kv(k, gs)
    vc = L.repeat_kv(v, gs)
    blk = jnp.take_along_axis(tables, jnp.clip(qpos // bs, 0, NB - 1), axis=1)
    wblk = jnp.where(wvalid & (blk >= 0), blk, P)    # P = drop block
    woff = jnp.where(wvalid, qpos % bs, 0)
    new_k = layer_cache["k"].at[wblk, woff].set(kc, mode="drop")
    new_v = layer_cache["v"].at[wblk, woff].set(vc, mode="drop")

    # dynamic per-layer window (hymba's traced is_global): 0 = none
    window = (jnp.where(is_global, 0, cfg.swa_window).astype(jnp.int32)
              if cfg.swa_window > 0 else None)

    def gather(*args):
        return _paged_gather_context(cfg, *args[:-1], window, xn.dtype)

    def kernel(*args):
        return _paged_kernel_context(cfg, *args, window)

    args = (q, new_k, new_v, tables, qpos, wvalid)
    if C == 1 and pages_in_place(gs, hd):
        ctx = lax.platform_dependent(*args, tpu=kernel, default=gather)
    else:
        ctx = gather(*args)
    out = L.attn_output(p, ctx, xn.dtype)
    return out, {"k": new_k, "v": new_v}


def _paged_backbone(cfg, params, x, tables, qpos, wvalid, cache, flags, *,
                    mode="decode", rows=None, pos0=None, n_valid=None):
    """Scan the blocks over the paged pool (cache rides the scan carry
    exactly like :func:`make_decode_step` — XLA aliases the donated pool
    end-to-end). KV goes through block tables; carried state (conv/ssm)
    is row-aligned: ``mode="decode"`` updates it full-width in place
    (parked rows keep their state via a ``where`` select), ``mode="chunk"``
    gathers the prefilling subset at ``rows`` and scatters the advanced
    state back with a drop-mode write (padding rows aim at the
    out-of-range row)."""
    B = x.shape[0]

    def ssm_step(p_l, cache_l, xn):
        state = {"conv": cache_l["conv"], "ssm": cache_l["ssm"]}
        if mode == "decode":
            out, st = mamba.ssm_decode_step(p_l["ssm"], xn, state, cfg)
            live = qpos[:, 0] >= 0
            st = {k: jnp.where(live.reshape((B,) + (1,) * (v.ndim - 1)),
                               v, state[k].astype(v.dtype)).astype(
                                   state[k].dtype)
                  for k, v in st.items()}
            return out, st
        # chunk: gather the carried state of the prefilling rows (clip:
        # padding rows read row 0 and their writes drop), zero it at the
        # first chunk of a prompt (a ``where`` select, not a multiply, so
        # a stale row's garbage can never leak into a fresh prompt)
        gathered = {k: jnp.take(v, rows, axis=0, mode="clip")
                    for k, v in state.items()}
        fresh = pos0 == 0
        gathered = {k: jnp.where(
            fresh.reshape((rows.shape[0],) + (1,) * (v.ndim - 1)),
            jnp.zeros_like(v), v) for k, v in gathered.items()}
        out, st = mamba.ssm_apply_chunk(p_l["ssm"], xn, cfg, gathered,
                                        n_valid)
        st = {k: state[k].at[rows].set(v.astype(state[k].dtype),
                                       mode="drop")
              for k, v in st.items()}
        return out, st

    def body(carry, xs):
        h, cch = carry
        p_l, flag, idx = xs
        cache_l = _layer_slice(cch, idx)
        new_cache: Dict[str, Any] = {}
        xn = L.apply_norm(h, p_l["ln1"], cfg)
        if cfg.block == BLOCK_SSM:
            out, st = ssm_step(p_l, cache_l, xn)
            h = h + out
            new_cache.update(st)
        elif cfg.block == BLOCK_HYBRID:
            a_out, a_cache = _paged_attn(cfg, p_l["attn"], xn, cache_l,
                                         tables, qpos, wvalid, flag)
            s_out, st = ssm_step(p_l, cache_l, xn)
            a_out = L.rmsnorm(a_out, p_l["attn_out_norm"], eps=cfg.norm_eps)
            s_out = L.rmsnorm(s_out, p_l["ssm_out_norm"], eps=cfg.norm_eps)
            h = h + 0.5 * (a_out + s_out)
            new_cache.update(a_cache)
            new_cache.update(st)
        else:
            a_out, a_cache = _paged_attn(cfg, p_l["attn"], xn, cache_l,
                                         tables, qpos, wvalid, flag)
            h = h + a_out
            new_cache.update(a_cache)
        if cfg.block in (BLOCK_DENSE, BLOCK_HYBRID):
            h = h + L.mlp_apply(p_l["mlp"],
                                L.apply_norm(h, p_l["ln2"], cfg), cfg)
        elif cfg.block == BLOCK_MOE:
            m_out, _ = moe.moe_apply_dropless(
                p_l["moe"], L.apply_norm(h, p_l["ln2"], cfg), cfg)
            h = h + m_out
        return (h, _layer_put(cch, new_cache, idx)), None

    # the scan's own per-layer slicing of weights and pool lands under
    # "layers"; each sublayer's ops under its own scope within it
    with jax.named_scope("layers"):
        (x, new_cache), _ = lax.scan(
            body, (x, cache),
            (params["blocks"], flags, jnp.arange(cfg.num_layers)))
    return L.apply_norm(x, params["final_norm"], cfg), new_cache


def make_decode_step_paged(cfg: ModelConfig, knobs, tp: int):
    """Batched one-token decode through per-request block tables: the
    whole request-row batch advances in one call (no outer vmap — the
    pool is one shared buffer, so rows are batched natively with per-row
    positions). A negative (parked) position writes nothing and yields a
    garbage row the engine discards."""
    compute_dtype = L.dtype_of(knobs["compute_dtype"])
    flags = layer_flags(cfg)

    def decode_step(params, cache, tokens, positions, block_tables):
        """tokens (B,1) int32, positions (B,) int32, block_tables (B,NB)
        int32 -> (logits (B,Vp), cache)."""
        x = embed_tokens(cfg, params, tokens, compute_dtype)
        qpos = positions[:, None]                     # (B, 1)
        wvalid = (positions >= 0)[:, None]
        x, new_cache = _paged_backbone(cfg, params, x, block_tables, qpos,
                                       wvalid, cache, flags)
        return _paged_logits(cfg, params, x[:, 0, :], compute_dtype), \
            new_cache

    return decode_step


def make_verify_step_paged(cfg: ModelConfig, knobs, tp: int):
    """K-token teacher-forced decode through block tables (speculative
    verify, DESIGN.md §14): feed the q-block [current token, draft_1 ..
    draft_{K-1}] in ONE dispatch, write the K KV rows with the same
    drop-mode scatters as chunked prefill, and return full-width logits —
    ``logits[:, j]`` is the target's next-token distribution after
    consuming tokens ``.. j``, which is exactly what the acceptance rule
    compares the drafts against. Rollback after a rejection is purely
    structural: the engine advances the row's length by the accepted
    count only, and the stale draft rows beyond it are out-causal-range
    (``kpos <= qpos``) until the next dispatch overwrites them — no
    blanking dispatch exists.

    Dense/MoE families only: recurrent carried state (SSM/hybrid conv +
    scan state) advances through *rejected* tokens and cannot be rolled
    back by a length decrement, so the registry gates this path off for
    carried-state families (``Capabilities.speculative``)."""
    compute_dtype = L.dtype_of(knobs["compute_dtype"])
    flags = layer_flags(cfg)

    def verify_step(params, cache, tokens, positions, block_tables,
                    n_valid):
        """tokens (B,K) int32 — token j of row b at absolute position
        ``positions[b] + j``; positions (B,) int32 (negative = parked
        row, writes nothing); block_tables (B,NB); n_valid (B,) live
        queries per row (<= K; trailing queries are padding) ->
        (logits (B,K,Vp), cache)."""
        B, K = tokens.shape
        x = embed_tokens(cfg, params, tokens, compute_dtype)
        qpos = positions[:, None] + jnp.arange(K)[None, :]
        wvalid = ((jnp.arange(K)[None, :] < n_valid[:, None])
                  & (positions >= 0)[:, None])
        x, new_cache = _paged_backbone(cfg, params, x, block_tables, qpos,
                                       wvalid, cache, flags)
        return _paged_logits(cfg, params, x, compute_dtype), new_cache

    return verify_step


def make_prefill_chunk_paged(cfg: ModelConfig, knobs, tp: int):
    """Fixed-shape chunked prompt deposit through block tables: up to B
    chunk-rows from different requests write straight into the shared
    pool (no gather/scatter of slot rows — the block table IS the
    indirection). Padding rows carry an all ``-1`` table and
    ``n_valid == 0``: every write drops, and their logits are garbage the
    engine aims at its drop row. ``rows`` carries each chunk-row's engine
    request-row index so recurrent carried state (SSM/hybrid) resumes
    from — and advances — the right (L, num_rows, ...) state row; padding
    rows aim at the out-of-range row index and their state writes drop
    (DESIGN.md §13)."""
    compute_dtype = L.dtype_of(knobs["compute_dtype"])
    flags = layer_flags(cfg)

    def prefill_chunk(params, cache, tokens, block_tables, rows, pos0,
                      n_valid):
        """tokens (B,C) int32; block_tables (B,NB); rows, pos0, n_valid
        (B,) -> (last-valid-position logits (B,Vp), cache)."""
        B, C = tokens.shape
        x = embed_tokens(cfg, params, tokens, compute_dtype)
        qpos = pos0[:, None] + jnp.arange(C)[None, :]
        wvalid = jnp.arange(C)[None, :] < n_valid[:, None]
        x, new_cache = _paged_backbone(cfg, params, x, block_tables, qpos,
                                       wvalid, cache, flags, mode="chunk",
                                       rows=rows, pos0=pos0, n_valid=n_valid)
        last = jnp.clip(n_valid - 1, 0, C - 1)
        hidden = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        return _paged_logits(cfg, params, hidden, compute_dtype), new_cache

    return prefill_chunk


def make_clone_block(cfg: ModelConfig, knobs, tp: int):
    """Device-side copy-on-write clone of one pool block (prefix caching,
    DESIGN.md §12): duplicate block ``src``'s pages — every layer, k and
    v — into block ``dst``, leaving the rest of the pool untouched.

    The prefix cache leases a *partially* matching cached block to the
    admitting request; the request's chunked prefill then resumes at a
    nonzero offset inside the cloned block and overwrites only the
    divergent tail positions (``prefill_chunk_paged`` already takes
    per-row ``pos0``, so resuming mid-block needs no model change — the
    clone is the one new device op the CoW path requires). The shared
    source block is never written.
    """
    del cfg, knobs, tp      # the pool layout is shape-polymorphic here

    def clone_block(cache, src, dst):
        """cache k/v (L, P, bs, Gs, hd); src/dst scalar int32 -> cache."""
        # block-indexed scatter: like every pool write, an out-of-range
        # destination drops instead of clamping onto a live block
        return {"k": cache["k"].at[:, dst].set(cache["k"][:, src],
                                               mode="drop"),
                "v": cache["v"].at[:, dst].set(cache["v"][:, src],
                                               mode="drop")}

    return clone_block


def _chunk_attn(cfg, p, xn, layer_cache, qpos, valid, is_global):
    """Attention for a prompt chunk against (and into) the cache:
    :func:`_cached_attn` with invalid (padding) positions aimed at the
    drop slot ``W`` — they write no cache pages and, never becoming valid
    cache entries, draw no attention weight from valid queries."""
    W = layer_cache["k"].shape[1]
    wslot = jnp.where(valid, qpos % W, W)
    return _cached_attn(cfg, p, xn, layer_cache, qpos, wslot, is_global)


def make_prefill_chunk(cfg: ModelConfig, knobs, tp: int):
    """Fixed-shape incremental prefill: deposit ``C`` prompt tokens into a
    per-request cache starting at position ``pos0``.

    Unlike :func:`make_prefill` (whose jit shape — and therefore XLA
    compile — depends on the prompt length), this step is always traced at
    the chunk shape, so serving compiles O(1) programs however many
    distinct prompt lengths the traffic carries. The last (partial) chunk
    is padded to ``C`` and masked via ``n_valid``: padding positions never
    write cache entries and never receive attention weight from valid
    queries. Returns the logits at the last *valid* position (only
    meaningful on the final chunk of a prompt) plus the updated cache.

    Supported for every decoder-only block family without a modality
    frontend. Dense attention deposits KV; SSM/hybrid thread their
    recurrent carried state (conv window + SSM state, living in the same
    per-request cache pytree) through :func:`mamba.ssm_apply_chunk`, so a
    prompt split at any ``cfg.ssm_chunk`` multiple resumes the scan
    bit-exactly; MoE routes per-token (:func:`moe.moe_apply_dropless`) so
    chunk boundaries cannot change routing (DESIGN.md §13). Only the
    patch_stub modality frontend stays monolithic — its prepended
    frontend tokens have no chunk deposit path (the registry exposes
    ``prefill_chunk=None`` and the capability flags name the reason).
    """
    compute_dtype = L.dtype_of(knobs["compute_dtype"])
    flags = layer_flags(cfg)

    def prefill_chunk(params, cache, tokens, pos0, n_valid):
        """tokens (C,) int32, pos0/n_valid scalar int32, cache a
        per-request (batch=1) pytree -> (logits (Vp,), cache)."""
        C = tokens.shape[0]
        x = embed_tokens(cfg, params, tokens[None], compute_dtype)  # (1,C,d)
        qpos = pos0 + jnp.arange(C)
        valid = jnp.arange(C) < n_valid

        def ssm_chunk(p_l, cache_l, xn):
            # carried state rides the per-request cache; a first chunk
            # (pos0 == 0) starts from zeros via a select, so a recycled
            # slot's stale state can never leak into a fresh prompt
            state = {"conv": cache_l["conv"], "ssm": cache_l["ssm"]}
            state = {k: jnp.where(pos0 == 0, jnp.zeros_like(v), v)
                     for k, v in state.items()}
            out, st = mamba.ssm_apply_chunk(
                p_l["ssm"], xn, cfg, state, jnp.asarray(n_valid).reshape(1))
            return out, st

        def body(carry, xs):
            h, cch = carry
            p_l, flag, idx = xs
            cache_l = _layer_slice(cch, idx)
            new_cache: Dict[str, Any] = {}
            xn = L.apply_norm(h, p_l["ln1"], cfg)
            if cfg.block == BLOCK_SSM:
                out, st = ssm_chunk(p_l, cache_l, xn)
                h = h + out
                new_cache.update(st)
            elif cfg.block == BLOCK_HYBRID:
                a_out, a_cache = _chunk_attn(cfg, p_l["attn"], xn, cache_l,
                                             qpos, valid, flag)
                s_out, st = ssm_chunk(p_l, cache_l, xn)
                a_out = L.rmsnorm(a_out, p_l["attn_out_norm"],
                                  eps=cfg.norm_eps)
                s_out = L.rmsnorm(s_out, p_l["ssm_out_norm"],
                                  eps=cfg.norm_eps)
                h = h + 0.5 * (a_out + s_out)
                new_cache.update(a_cache)
                new_cache.update(st)
            else:
                a_out, a_cache = _chunk_attn(cfg, p_l["attn"], xn, cache_l,
                                             qpos, valid, flag)
                h = h + a_out
                new_cache.update(a_cache)
            if cfg.block in (BLOCK_DENSE, BLOCK_HYBRID):
                h = h + L.mlp_apply(p_l["mlp"],
                                    L.apply_norm(h, p_l["ln2"], cfg), cfg)
            elif cfg.block == BLOCK_MOE:
                m_out, _ = moe.moe_apply_dropless(
                    p_l["moe"], L.apply_norm(h, p_l["ln2"], cfg), cfg)
                h = h + m_out
            return (h, _layer_put(cch, new_cache, idx)), None

        (x, new_cache), _ = lax.scan(
            body, (x, cache),
            (params["blocks"], flags, jnp.arange(cfg.num_layers)))
        x = L.apply_norm(x, params["final_norm"], cfg)
        last = jnp.clip(n_valid - 1, 0, C - 1)
        hidden = jnp.take(x[0], last, axis=0)                   # (d,)
        w_out = lm_head_weight(cfg, params).astype(compute_dtype)
        logits = (hidden @ w_out).astype(jnp.float32)
        vocab_ok = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        return jnp.where(vocab_ok, logits, L.NEG_INF), new_cache

    return prefill_chunk
