"""Operations and bytes that the algorithm needs for one dispatch.

Counted from the model's published shapes and each row's resident
context, never from how the program computes it:

* FLOPs count the experts a token is routed to (top-k), not all of them
  (the matmul arithmetic of ``roofline/flops.py::_per_token_matmul_flops``);
  attention counts each query against the keys it may see (causal), and
  the LM head only where a next token is due;
* KV bytes count each row's resident context, read once, and the new
  tokens written once, not the width of its block table;
* weight bytes count every weight the dispatch needs, read once: for a
  mixture of experts, the experts that ``T`` tokens are expected to touch,
  ``E * (1 - (1 - k/E) ** T)`` of them in each layer.

So a faster kernel raises a share honestly, and no share can pass 100%
unless the time leaves out part of the work.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Iterable, Tuple

PEAKS_FILE = Path(__file__).with_name("peaks.json")
BF16 = 2


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
        return 2 * self.kv_heads * self.head_dim * BF16 * self.layers

    def weight_bytes(self, tokens: int) -> float:
        """Weights a dispatch of ``tokens`` tokens needs, each read once."""
        per = self.attn_proj_params * BF16 + 4 * self.d * BF16   # + norms
        if self.experts:
            touched = self.experts * (1 - (1 - self.top_k / self.experts)
                                      ** tokens)
            per += touched * self.ffn_params_per_expert * BF16
            per += self.d * self.experts * 4                     # f32 router
        else:
            per += self.ffn_params_per_expert * BF16
        head = self.d * self.vocab * BF16
        return per * self.layers + head + tokens * self.d * BF16


def decode_work(dims: Dims, contexts: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode dispatch whose rows see ``contexts``
    keys each (the resident tokens plus the one being decoded)."""
    ctx = list(contexts)
    flops = sum(dims.body_flops_per_token + dims.head_flops
                + dims.attn_flops(c) for c in ctx)
    kv = sum(ctx) * dims.kv_bytes_per_token
    return flops, dims.weight_bytes(len(ctx)) + kv


def chunk_work(dims: Dims, rows: Iterable[Tuple[int, int]]
               ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one prefill-chunk dispatch over ``rows`` of
    ``(pos0, n)``: ``n`` prompt tokens from position ``pos0``, with the
    LM head at each row's last token."""
    flops = 0.0
    kv_tokens = 0
    tokens = 0
    for pos0, n in rows:
        # query i (0-based) sees pos0 + i + 1 keys: sum over the chunk
        seen = n * pos0 + n * (n + 1) // 2
        flops += (n * dims.body_flops_per_token + dims.head_flops
                  + dims.attn_flops(1) * seen)
        kv_tokens += pos0 + n
        tokens += n
    return flops, dims.weight_bytes(tokens) + kv_tokens * dims.kv_bytes_per_token


def load_peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path.name}; known: {sorted(table)}")
    return table[device_kind]


def least_time(flops: float, nbytes: float, peaks: dict
               ) -> Tuple[float, str]:
    """The least seconds the chip could take, and which bound sets it."""
    tc = flops / peaks["bf16_flops"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
