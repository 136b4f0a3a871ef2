"""Qwen3 (dense GQA with per-head QK-norm, no biases): everything the
harness knows of this architecture, as ``models/<model_type>.py`` provides
it for the ``model_type`` of a configuration file.

* ``program_config(config)``: the program's ``ModelConfig`` for the
  file's published keys;
* ``logits(config, weights, tokens, first, quant=None)``: the plain
  reference (``reference.forward`` over this module's layer);
* ``counts(config)``: the work counts, whose ``decode_work(contexts)``
  and ``chunk_work(rows)`` give (FLOPs, bytes) of one dispatch.

A layer is pre-norm: ``h + attention(rms(h))``, then ``h + ffn(rms(h))``,
the feed-forward a SwiGLU MLP or, where the block holds ``moe``, top-k
routed SwiGLU experts (as OLMoE, whose module reuses this one). Weights
in the layout of the program's parameter tree: ``attn`` wq (d, H, hd),
wk/wv (d, KV, hd), wo (H, hd, d), per-head ``q_norm``/``k_norm``; ``mlp``
or ``moe`` (router (d, E), experts (E, d, f)/(E, f, d)); ``ln1``/``ln2``.
"""

from __future__ import annotations

import dataclasses
import functools
import json
from dataclasses import dataclass
from typing import Iterable, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.chip import reference as R
from benchmarks.chip import work as W

#: program config field for each published key of a configuration file
FIELDS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
          "num_attention_heads": "num_heads",
          "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
          "num_experts": "num_experts", "num_experts_per_tok": "top_k",
          "tie_word_embeddings": "tie_embeddings"}


def mapped(config: dict, **fixed):
    """The program's config of the family ``config["arch"]`` names, with
    each key of ``FIELDS`` that the file gives, and the fields
    ``fixed``."""
    from repro.configs import get_config

    c = dict(config["config"])
    c.setdefault("head_dim", c["hidden_size"] // c["num_attention_heads"])
    base = get_config(config["arch"])
    return dataclasses.replace(base, **{
        f: type(getattr(base, f))(c[k]) for k, f in FIELDS.items()
        if k in c}, **fixed)


def program_config(config: dict):
    c = config["config"]
    if c.get("num_experts") and not c.get("norm_topk_prob", True):
        raise ValueError("the program always renormalises the top-k gates; "
                         "a configuration with norm_topk_prob false cannot "
                         "run as stated")
    if c.get("attention_bias"):
        raise ValueError("the program's Qwen3 block has no projection "
                         "biases; attention_bias true cannot run as stated")
    return mapped(config, qk_norm=True, qkv_bias=False)


# -- reference ---------------------------------------------------------

def _attention(c, p, x, quant, dt):
    """Causal self-attention of the whole sequence x (T, d)."""
    T = x.shape[0]
    eps = c["rms_norm_eps"]
    pos = jnp.arange(T)
    q = R._mm("td,dhk->thk", x, R._weight(p["wq"], (0,), quant), dt)
    k = R._mm("td,dhk->thk", x, R._weight(p["wk"], (0,), quant), dt)
    v = R._mm("td,dhk->thk", x, R._weight(p["wv"], (0,), quant), dt)
    q = R._rope(R._rms(q, p["q_norm"], eps, dt), pos, c["rope_theta"], dt)
    k = R._rope(R._rms(k, p["k_norm"], eps, dt), pos, c["rope_theta"], dt)
    ctx = R.attention(q, k, v, dt)
    return R._mm("thk,hkd->td", ctx, R._weight(p["wo"], (0, 1), quant), dt)


def _mlp(c, p, x, quant, dt):
    wg = R._weight(p["w_gate"], (0,), quant)
    wu = R._weight(p["w_up"], (0,), quant)
    wd = R._weight(p["w_down"], (0,), quant)

    def block(xb):
        h = jax.nn.silu(R._mm("td,df->tf", xb, wg, dt)) * \
            R._mm("td,df->tf", xb, wu, dt)
        return R._mm("tf,fd->td", h, wd, dt)

    return R.by_token_blocks(block, x)


def _moe(c, p, x, quant, dt):
    """Top-k routing over all experts per token: the router's logits kept
    in float32 (from inputs and weights in the stated type), softmax gates
    in float32, the top-k renormalised when ``norm_topk_prob`` says so."""
    E, K = c["num_experts"], c["num_experts_per_tok"]
    router = R._weight(p["router"], (0,), quant)
    wg = R._weight(p["w_gate"], (1,), quant)
    wu = R._weight(p["w_up"], (1,), quant)
    wd = R._weight(p["w_down"], (1,), quant)

    def block(xb):
        logits = jnp.einsum("td,de->te", xb.astype(dt), router.astype(dt),
                            precision=R.HIGHEST, preferred_element_type=R.F32)
        top, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), K)
        if c.get("norm_topk_prob"):
            top = top / jnp.sum(top, axis=-1, keepdims=True)
        gates = jnp.sum(jax.nn.one_hot(idx, E) * top[..., None], axis=1)
        h = jax.nn.silu(R._mm("td,edf->tef", xb, wg, dt)) * \
            R._mm("td,edf->tef", xb, wu, dt)
        return R._mm("te,ted->td", gates,
                     R._mm("tef,efd->ted", h, wd, dt), dt)

    return R.by_token_blocks(block, x)


@functools.partial(jax.jit, static_argnames=("c", "quant", "attention"))
def layer(h, blocks, i, *, c, quant, attention=_attention):
    """The residual stream after pre-norm layer ``i``; ``attention`` is
    the block's attention (this module's, or a variant's)."""
    c = json.loads(c)
    dt = jnp.dtype(c["torch_dtype"])
    p = jax.tree_util.tree_map(lambda a: a[i], blocks)
    eps = c["rms_norm_eps"]
    h = h + attention(c, p["attn"], R._rms(h, p["ln1"]["w"], eps, dt), quant,
                      dt)
    xn = R._rms(h, p["ln2"]["w"], eps, dt)
    ffn = _moe(c, p["moe"], xn, quant, dt) if "moe" in p else _mlp(
        c, p["mlp"], xn, quant, dt)
    return h + ffn


def logits(config: dict, weights, tokens, first: int, quant=None):
    """``reference.forward`` over this module's layer."""
    return R.forward(config, weights, tokens, first, quant, layer)


# -- work counts -------------------------------------------------------

@dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    experts: int = 0
    top_k: int = 0

    @classmethod
    def of(cls, config: dict) -> "Dims":
        """From a configuration's published keys (``configs/*.json``)."""
        c = config["config"]
        heads = c["num_attention_heads"]
        return cls(layers=c["num_hidden_layers"], d=c["hidden_size"],
                   heads=heads, kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim") or c["hidden_size"] // heads,
                   ffn=c["intermediate_size"], vocab=c["vocab_size"],
                   experts=c.get("num_experts", 0),
                   top_k=c.get("num_experts_per_tok", 0))

    # -- per token -----------------------------------------------------
    @property
    def attn_proj_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return 2 * self.d * q + 2 * self.d * kv

    @property
    def ffn_params_per_expert(self) -> int:
        return 3 * self.d * self.ffn          # gate, up, down (SwiGLU)

    @property
    def body_flops_per_token(self) -> float:
        """Matmul FLOPs of one token through every layer, without the
        LM head: projections, routed experts and the router."""
        per = 2 * self.attn_proj_params
        if self.experts:
            per += 2 * self.top_k * self.ffn_params_per_expert
            per += 2 * self.d * self.experts
        else:
            per += 2 * self.ffn_params_per_expert
        return float(per * self.layers)

    @property
    def head_flops(self) -> float:
        return 2.0 * self.d * self.vocab

    def attn_flops(self, ctx: int) -> float:
        """QK and PV FLOPs of one query that sees ``ctx`` keys."""
        return 4.0 * self.heads * self.head_dim * ctx * self.layers

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.kv_heads * self.head_dim * W.BF16 * self.layers

    def weight_bytes(self, tokens: int) -> float:
        """Weights a dispatch of ``tokens`` tokens needs, each read once."""
        per = self.attn_proj_params * W.BF16 + 4 * self.d * W.BF16  # + norms
        if self.experts:
            touched = self.experts * (1 - (1 - self.top_k / self.experts)
                                      ** tokens)
            per += touched * self.ffn_params_per_expert * W.BF16
            per += self.d * self.experts * 4                     # f32 router
        else:
            per += self.ffn_params_per_expert * W.BF16
        head = self.d * self.vocab * W.BF16
        return per * self.layers + head + tokens * self.d * W.BF16

    # -- per dispatch --------------------------------------------------
    def decode_work(self, contexts: Iterable[int]) -> Tuple[float, float]:
        """(FLOPs, bytes) of one decode dispatch whose rows see
        ``contexts`` keys each (the resident tokens plus the one being
        decoded)."""
        ctx = list(contexts)
        flops = sum(self.body_flops_per_token + self.head_flops
                    + self.attn_flops(c) for c in ctx)
        kv = sum(ctx) * self.kv_bytes_per_token
        return flops, self.weight_bytes(len(ctx)) + kv

    def chunk_work(self, rows: Iterable[Tuple[int, int]]
                   ) -> Tuple[float, float]:
        """(FLOPs, bytes) of one prefill-chunk dispatch over ``rows`` of
        ``(pos0, n)``: ``n`` prompt tokens from position ``pos0``, with
        the LM head at each row's last token."""
        flops = 0.0
        kv_tokens = 0
        tokens = 0
        for pos0, n in rows:
            flops += (n * self.body_flops_per_token + self.head_flops
                      + self.attn_flops(1) * W.keys_seen(pos0, n))
            kv_tokens += pos0 + n
            tokens += n
        return flops, (self.weight_bytes(tokens)
                       + kv_tokens * self.kv_bytes_per_token)


def counts(config: dict) -> Dims:
    return Dims.of(config)
