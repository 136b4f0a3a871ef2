"""Plain reference of the served models: the full causal forward pass in
``jax.numpy``, with no cache, no batching and no kernel. It imports
nothing of the program.

It follows the configuration file's published keys (``configs/*.json``)
and reads the weights the harness drew, in the layout of the program's
parameter tree (``embed`` (V, d); ``blocks`` stacked over layers with
``attn`` wq (d, H, hd), wk/wv (d, KV, hd), wo (H, hd, d), per-head
``q_norm``/``k_norm``; ``mlp`` or ``moe`` (router (d, E), experts
(E, d, f)/(E, f, d)); ``final_norm``; ``lm_head`` (d, V)). Departures of
the program from the published model that the configuration states under
``departures`` are followed here too.

It computes in the precision the configuration states (``torch_dtype``,
bfloat16 here), the way a bfloat16 model is run (as the published
models' own ``transformers`` code runs them): every weight is held in
that type, every matmul (attention scores and the LM head included)
accumulates in float32 and rounds its output once, except the MoE
router's, whose logits stay in float32 as routers are run; norms,
rotary embedding and the softmaxes compute in float32 and round their
output; the residual stream is held in the stated type.

``quant="fp8"`` is the control: every weight that enters a matmul is
rounded to float8 e4m3 with one scale per output channel, the step below
the bfloat16 the configurations state.

Runs layer by layer over the whole sequence, queries and tokens in
blocks, so that it fits beside the weights once the engine is freed.
Sequence lengths are padded to ``PAD`` (later positions never reach
earlier ones under the causal mask), so few shapes compile.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
F32 = jnp.float32
PAD = 1024          # sequence lengths are padded to a multiple of this
Q_BLOCK = 256       # queries per attention block
T_BLOCK = 512       # tokens per feed-forward block
NEG = -1e30


def _mm(spec, x, w, dt):
    """Matmul of ``dt`` inputs, accumulated in float32, rounded to ``dt``."""
    return jnp.einsum(spec, x.astype(dt), w.astype(dt), precision=HIGHEST,
                      preferred_element_type=F32).astype(dt)


def _fp8(w, in_axes):
    """``w`` rounded to float8 e4m3 with a scale per output channel (the
    max over the contracted ``in_axes`` maps to the format's largest)."""
    w = w.astype(F32)
    amax = jnp.max(jnp.abs(w), axis=in_axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 448.0
    return (w / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _weight(w, in_axes, quant):
    return _fp8(w, in_axes) if quant == "fp8" else w


def _rms(x, w, eps, dt):
    x = x.astype(F32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * lax.rsqrt(var + eps) * w.astype(F32)).astype(dt)


def _rope(x, pos, theta, dt):
    """Half-split rotary embedding (as Qwen3 and OLMoE). x (T, H, hd)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * inv
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(dt)


def _attention(c, p, x, quant, dt):
    """Causal self-attention of the whole sequence x (T, d)."""
    T = x.shape[0]
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // H
    eps = c["rms_norm_eps"]
    pos = jnp.arange(T)
    q = _mm("td,dhk->thk", x, _weight(p["wq"], (0,), quant), dt)
    k = _mm("td,dhk->thk", x, _weight(p["wk"], (0,), quant), dt)
    v = _mm("td,dhk->thk", x, _weight(p["wv"], (0,), quant), dt)
    q = _rope(_rms(q, p["q_norm"], eps, dt), pos, c["rope_theta"], dt)
    k = _rope(_rms(k, p["k_norm"], eps, dt), pos, c["rope_theta"], dt)
    qg = q.reshape(T // Q_BLOCK, Q_BLOCK, KV, H // KV, hd)

    def block(args):
        qb, start = args
        s = _mm("qgrk,tgk->grqt", qb, k, dt).astype(F32) / math.sqrt(hd)
        qpos = start + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, :] <= qpos[:, None], s, NEG)
        return _mm("grqt,tgk->qgrk", jax.nn.softmax(s, axis=-1), v, dt)

    ctx = lax.map(block, (qg, jnp.arange(T // Q_BLOCK) * Q_BLOCK))
    ctx = ctx.reshape(T, H, hd)
    return _mm("thk,hkd->td", ctx, _weight(p["wo"], (0, 1), quant), dt)


def _mlp(c, p, x, quant, dt):
    wg = _weight(p["w_gate"], (0,), quant)
    wu = _weight(p["w_up"], (0,), quant)
    wd = _weight(p["w_down"], (0,), quant)

    def block(xb):
        h = jax.nn.silu(_mm("td,df->tf", xb, wg, dt)) * \
            _mm("td,df->tf", xb, wu, dt)
        return _mm("tf,fd->td", h, wd, dt)

    T, d = x.shape
    return lax.map(block, x.reshape(T // T_BLOCK, T_BLOCK, d)).reshape(T, d)


def _moe(c, p, x, quant, dt):
    """Top-k routing over all experts per token: the router's logits kept
    in float32 (from inputs and weights in the stated type), softmax gates
    in float32, the top-k renormalised when ``norm_topk_prob`` says so."""
    E, K = c["num_experts"], c["num_experts_per_tok"]
    router = _weight(p["router"], (0,), quant)
    wg = _weight(p["w_gate"], (1,), quant)
    wu = _weight(p["w_up"], (1,), quant)
    wd = _weight(p["w_down"], (1,), quant)

    def block(xb):
        logits = jnp.einsum("td,de->te", xb.astype(dt), router.astype(dt),
                            precision=HIGHEST, preferred_element_type=F32)
        top, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), K)
        if c.get("norm_topk_prob"):
            top = top / jnp.sum(top, axis=-1, keepdims=True)
        gates = jnp.sum(jax.nn.one_hot(idx, E) * top[..., None], axis=1)
        h = jax.nn.silu(_mm("td,edf->tef", xb, wg, dt)) * \
            _mm("td,edf->tef", xb, wu, dt)
        return _mm("te,ted->td", gates, _mm("tef,efd->ted", h, wd, dt), dt)

    T, d = x.shape
    return lax.map(block, x.reshape(T // T_BLOCK, T_BLOCK, d)).reshape(T, d)


@functools.partial(jax.jit, static_argnames=("c", "quant"))
def _layer(h, blocks, layer, *, c, quant):
    c = dict(c)
    dt = jnp.dtype(c["torch_dtype"])
    p = jax.tree_util.tree_map(lambda a: a[layer], blocks)
    eps = c["rms_norm_eps"]
    h = h + _attention(c, p["attn"], _rms(h, p["ln1"]["w"], eps, dt), quant,
                       dt)
    xn = _rms(h, p["ln2"]["w"], eps, dt)
    ffn = _moe(c, p["moe"], xn, quant, dt) if "moe" in p else _mlp(
        c, p["mlp"], xn, quant, dt)
    return h + ffn


@functools.partial(jax.jit, static_argnames=("quant", "dt"))
def _embed(embed, tokens, *, quant, dt):
    return _weight(embed[tokens], (1,), quant).astype(dt)


@functools.partial(jax.jit, static_argnames=("c", "quant", "n"))
def _head(h, first, final_norm, lm_head, *, c, quant, n):
    """Logits (float32) of the ``n`` rows of h from ``first``."""
    c = dict(c)
    dt = jnp.dtype(c["torch_dtype"])
    x = _rms(lax.dynamic_slice_in_dim(h, first, n), final_norm["w"],
             c["rms_norm_eps"], dt)
    d, V = lm_head.shape
    nb = 8 if V % 8 == 0 else 1
    vb = V // nb

    def block(i):
        w = lax.dynamic_slice_in_dim(lm_head, i * vb, vb, axis=1)
        return _mm("td,dv->tv", x, _weight(w, (0,), quant), dt).astype(F32)

    out = lax.map(block, jnp.arange(nb))             # (nb, n, vb)
    logits = out.transpose(1, 0, 2).reshape(n, V)
    return jnp.where(jnp.arange(V) < c["vocab_size"], logits, NEG)


def _pad(n: int, m: int) -> int:
    return -(-n // m) * m


def logits(config: dict, weights, tokens: np.ndarray, first: int,
           quant: str | None = None):
    """Next-token logits (float32, on the device) at positions ``first ..
    len(tokens) - 1`` of the sequence ``tokens``, as rows ``0 .. n-1`` of
    an ``(n rounded up to 128, V)`` array; padded vocabulary columns
    hold -1e30."""
    c = tuple(sorted((k, v) for k, v in config["config"].items()
                     if not isinstance(v, (dict, list))))
    T = len(tokens)
    n = T - first
    n_pad = _pad(n, 128)
    padded = np.zeros((_pad(T + 128, PAD),), np.int32)
    padded[:T] = tokens
    h = _embed(weights["embed"], jnp.asarray(padded), quant=quant,
               dt=str(config["config"]["torch_dtype"]))
    for layer in range(config["config"]["num_hidden_layers"]):
        h = _layer(h, weights["blocks"], jnp.int32(layer), c=c, quant=quant)
    return _head(h, jnp.int32(first), weights["final_norm"],
                 weights["lm_head"], c=c, quant=quant, n=n_pad)
