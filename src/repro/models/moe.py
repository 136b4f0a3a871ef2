"""Mixture-of-Experts FFN: GShard-style grouped top-k dispatch.

Tokens are processed in groups of ``moe_group_size``; within each group a
capacity-bounded one-hot dispatch tensor routes tokens to experts. This keeps
the (G, E, C) dispatch tensors small and SPMD-friendly — experts shard on the
"model" mesh axis, groups follow the batch sharding, and XLA inserts the
dispatch all-to-all/all-gather. Dropless within capacity_factor; overflow
tokens fall through on the residual path (standard Switch behaviour).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init


def init_moe(cfg, key, dtype=jnp.float32):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], (d, e), d, jnp.float32),  # router in f32
        "w_gate": dense_init(ks[1], (e, d, f), d, dtype),
        "w_up": dense_init(ks[2], (e, d, f), d, dtype),
        "w_down": dense_init(ks[3], (e, f, d), f, dtype),
    }
    return p


def expert_capacity(cfg, group: int) -> int:
    cap = int(group * cfg.top_k / cfg.num_experts * cfg.capacity_factor)
    return max(cap, cfg.top_k)


def _route_group(p, xg, cfg):
    """One token group: xg (G, d) -> (out (G, d), aux metrics)."""
    G, d = xg.shape
    E, K = cfg.num_experts, cfg.top_k
    C = expert_capacity(cfg, G)

    logits = jnp.einsum("gd,de->ge", xg.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)                      # (G, E)
    gate_vals, idx = jax.lax.top_k(probs, K)                     # (G, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)             # renormalize

    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)           # (G, K, E)
    # position of each (token, k) entry within its expert queue: priority by
    # k slot first (all first-choices before second-choices), then token order
    flat = onehot.transpose(1, 0, 2).reshape(K * G, E)           # (K*G, E)
    pos = jnp.cumsum(flat, axis=0) - flat                        # (K*G, E)
    pos = pos.reshape(K, G, E).transpose(1, 0, 2)                # (G, K, E)
    pos_in_expert = jnp.sum(pos * onehot, axis=-1)               # (G, K)
    fits = pos_in_expert < C
    kept = onehot * fits[..., None]                              # (G, K, E)

    pos_onehot = jax.nn.one_hot(pos_in_expert.astype(jnp.int32), C,
                                dtype=jnp.float32)               # (G,K,C)
    # dispatch (G, E, C) / combine (G, E, C)
    dispatch = jnp.einsum("gke,gkc->gec", kept, pos_onehot)
    combine = jnp.einsum("gke,gkc,gk->gec", kept, pos_onehot, gate_vals)

    cd = xg.dtype
    expert_in = jnp.einsum("gec,gd->ecd", dispatch.astype(cd), xg)  # (E,C,d)
    g = jnp.einsum("ecd,edf->ecf", expert_in, p["w_gate"].astype(cd))
    u = jnp.einsum("ecd,edf->ecf", expert_in, p["w_up"].astype(cd))
    h = jax.nn.silu(g) * u
    out_e = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(cd))
    out = jnp.einsum("gec,ecd->gd", combine.astype(cd), out_e)

    # Switch aux losses: load balance + router z-loss
    density = jnp.mean(onehot[:, 0, :], axis=0)                  # top-1 density
    density_proxy = jnp.mean(probs, axis=0)
    lb_loss = jnp.sum(density * density_proxy) * (E ** 2) / E
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    dropped = 1.0 - jnp.sum(kept) / (G * K)
    return out, (lb_loss, z_loss, dropped)


@jax.named_scope("moe")
def moe_apply(p, x, cfg):
    """x (B, S, d) -> (out (B, S, d), aux dict). Groups follow batch sharding.
    Ragged token counts are zero-row padded up to a group multiple (padded
    rows route but their outputs are discarded)."""
    B, S, d = x.shape
    n_tokens = B * S
    G = min(cfg.moe_group_size, n_tokens)
    flat = x.reshape(n_tokens, d)
    pad = (-n_tokens) % G
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
    xg = flat.reshape(-1, G, d)
    out, (lb, zl, dr) = jax.vmap(lambda t: _route_group(p, t, cfg))(xg)
    out = out.reshape(-1, d)
    if pad:
        out = out[:n_tokens]
    aux = {"moe_lb_loss": jnp.mean(lb), "moe_z_loss": jnp.mean(zl),
           "moe_dropped": jnp.mean(dr)}
    return out.reshape(B, S, d), aux


@jax.named_scope("moe")
def moe_apply_dropless(p, x, cfg):
    """Serve-time routing: per-token top-k with no capacity coupling.

    Every token independently picks its top-k experts and combines their
    outputs under renormalized gates — no grouping, no position-in-expert
    queue, no capacity drops — so a token's output is a function of that
    token's hidden state alone. That is the chunk-parity property the
    continuous engine needs: splitting a prompt at any chunk boundary, or
    batching it with any set of neighbours, cannot change its routing.

    Capacity-vs-parity tradeoff: without the capacity bound every expert
    runs on every token (the combine zero-weights the non-selected ones),
    costing num_experts/top_k x the grouped FLOPs and giving up the
    (G, E, C) all-to-all layout. Serving pays that for token-identical
    chunked prefill; training keeps :func:`moe_apply` for the
    capacity-bounded, load-balanced (aux-loss) regime.
    """
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.top_k
    flat = x.reshape(B * S, d)
    logits = jnp.einsum("td,de->te", flat.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)                      # (T, E)
    gate_vals, idx = jax.lax.top_k(probs, K)                     # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)             # renormalize
    weights = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32)
                      * gate_vals[..., None], axis=1)            # (T, E)
    cd = x.dtype
    g = jnp.einsum("td,edf->tef", flat, p["w_gate"].astype(cd))
    u = jnp.einsum("td,edf->tef", flat, p["w_up"].astype(cd))
    h = jax.nn.silu(g) * u
    out_e = jnp.einsum("tef,efd->ted", h, p["w_down"].astype(cd))
    out = jnp.einsum("te,ted->td", weights.astype(cd), out_e)
    aux = {"moe_lb_loss": jnp.zeros(()), "moe_z_loss": jnp.zeros(()),
           "moe_dropped": jnp.zeros(())}
    return out.reshape(B, S, d), aux
