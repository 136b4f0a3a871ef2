"""The architecture-neutral parts of the plain references: the full causal
forward pass in ``jax.numpy``, with no cache, no batching and no kernel.
It imports nothing of the program.

Each architecture's module (``models/<model_type>.py``) writes its own
layer from these pieces and runs it through :func:`forward`, which reads
the weights the harness drew in the layout of the program's parameter
tree (``embed`` (V, d); ``blocks`` stacked over layers; ``final_norm``;
``lm_head`` (d, V)).

Computing in the precision the configuration states (``torch_dtype``),
the way a bfloat16 model is run (as the published models' own
``transformers`` code runs them): every weight is held in that type,
every matmul (attention scores and the LM head included) accumulates in
float32 and rounds its output once (:func:`_mm`); norms, rotary
embedding and the softmaxes compute in float32 and round their output;
the residual stream is held in the stated type.

``quant="fp8"`` is the control: every weight that enters a matmul is
rounded to float8 e4m3 with one scale per output channel
(:func:`_weight`), the step below the bfloat16 the configurations state.

Runs layer by layer over the whole sequence, queries and tokens in
blocks, so that it fits beside the weights once the engine is freed.
Sequence lengths are padded to ``PAD`` (later positions never reach
earlier ones under the causal mask), so few shapes compile.
"""

from __future__ import annotations

import functools
import json
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
    """Half-split rotary embedding (as Qwen and OLMoE). x (T, H, hd)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * inv
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(dt)


def attention(q, k, v, dt, window=None):
    """Causal attention of the whole sequence, in blocks of ``Q_BLOCK``
    queries: q (T, H, hd), k and v (T, KV, hd), grouped query heads
    (``H // KV`` to a kv head). With ``window``, query i sees keys
    ``i - window + 1 .. i`` only. Returns (T, H, hd)."""
    T, H, hd = q.shape
    KV = k.shape[1]
    pos = jnp.arange(T)
    qg = q.reshape(T // Q_BLOCK, Q_BLOCK, KV, H // KV, hd)

    def block(args):
        qb, start = args
        s = _mm("qgrk,tgk->grqt", qb, k, dt).astype(F32) / math.sqrt(hd)
        qpos = start + jnp.arange(Q_BLOCK)
        seen = pos[None, :] <= qpos[:, None]
        if window is not None:
            seen = seen & (pos[None, :] > qpos[:, None] - window)
        s = jnp.where(seen, s, NEG)
        return _mm("grqt,tgk->qgrk", jax.nn.softmax(s, axis=-1), v, dt)

    ctx = lax.map(block, (qg, jnp.arange(T // Q_BLOCK) * Q_BLOCK))
    return ctx.reshape(T, H, hd)


def by_token_blocks(fn, x):
    """``fn`` over x (T, d) in blocks of ``T_BLOCK`` tokens; ``fn`` maps a
    (T_BLOCK, d) block to a (T_BLOCK, d) block."""
    T, d = x.shape
    return lax.map(fn, x.reshape(T // T_BLOCK, T_BLOCK, d)).reshape(T, d)


@functools.partial(jax.jit, static_argnames=("quant", "dt"))
def _embed(embed, tokens, *, quant, dt):
    return _weight(embed[tokens], (1,), quant).astype(dt)


@functools.partial(jax.jit, static_argnames=("c", "quant", "n"))
def _head(h, first, final_norm, lm_head, *, c, quant, n):
    """Logits (float32) of the ``n`` rows of h from ``first``."""
    c = json.loads(c)
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


def forward(config: dict, weights, tokens: np.ndarray, first: int,
            quant: str | None, layer):
    """Next-token logits (float32, on the device) at positions ``first ..
    len(tokens) - 1`` of the sequence ``tokens``, as rows ``0 .. n-1`` of
    an ``(n rounded up to 128, V)`` array; padded vocabulary columns
    hold -1e30.

    ``layer(h, blocks, i, *, c, quant)`` is the architecture's layer,
    jitted with ``c`` (the file's ``config`` as JSON, every key and
    nested group in it) and ``quant`` static: it returns the residual
    stream h (T, d) after layer ``i`` of the stacked ``blocks``."""
    c = json.dumps(config["config"], sort_keys=True)
    T = len(tokens)
    n = T - first
    n_pad = _pad(n, 128)
    padded = np.zeros((_pad(T + 128, PAD),), np.int32)
    padded[:T] = tokens
    h = _embed(weights["embed"], jnp.asarray(padded), quant=quant,
               dt=str(config["config"]["torch_dtype"]))
    for i in range(config["config"]["num_hidden_layers"]):
        h = layer(h, weights["blocks"], jnp.int32(i), c=c, quant=quant)
    return _head(h, jnp.int32(first), weights["final_norm"],
                 weights["lm_head"], c=c, quant=quant, n=n_pad)
