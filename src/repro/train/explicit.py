"""Explicit (threadcomm) trainer: the paper's technique as a first-class
training feature, expressed through the unified ``Comm`` API.

The fwd/bwd runs inside a shard_map that is MANUAL over the unified data-
parallel rank space — process axes ("pod") × thread axes ("data") — exactly
the threadcomm construction: every (pod, data) coordinate is one unified
rank computing local gradients. Tensor parallelism ("model") stays auto.

Gradient sync is the paper's two-level hierarchical schedule, built from
DERIVED sub-communicators (DESIGN.md §2) and FUSED with a ZeRO-1 flat
optimizer:

    flat_g   = concat(all grad leaves)               # one flat f32 vector
    shard    = thread_comm.reduce_scatter(flat_g)    # fast domain (ICI)
    req      = process_comm.iallreduce(shard)        # slow domain, bytes/M,
                                                     #   issued on the "grad"
                                                     #   CommStream
    ... step bookkeeping overlaps the slow-domain sync ...
    shard    = req.wait()
    shard'   = AdamW(shard)                          # state lives as shards
    params   = unflatten(thread_comm.allgather(shard'))   # fast domain

so the inter-pod (slow) traffic is params/M bytes — the paper's "do the bulk
in the fast shared domain" insight — optimizer state is sharded over the
thread domain for free (ZeRO-1), and the slow-domain allreduce is a
nonblocking Request the step overlaps with local work (the MPIX-stream
pattern of arXiv:2208.13707).

grad_sync="flat" keeps the same state layout but reduces the FULL flat
vector over the root comm (process × thread) before slicing — the rank-
unaware MPI-everywhere baseline the paper compares against.

The root comm is activated in service mode (``comm.start()`` without a
``with``): the trainer is a long-lived parallel region, and the traced
requests/sub-comms stay inside its activation window.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import MeshConfig, TrainConfig
from repro.core.comm import threadcomm_init
from repro.core.compat import shard_map
from repro.dist.sharding import batch_pspec, named_sharding, param_pspecs
from repro.optim import cosine_schedule


class FlatAdamState(NamedTuple):
    step: jax.Array
    m: jax.Array        # (padded_len/DP,) f32 shard
    v: jax.Array
    master: jax.Array   # f32 master shard


class ExplicitTrainState(NamedTuple):
    params: Any         # model dtype, replicated over (pod, data), TP on model
    opt: FlatAdamState


def _tree_sizes(tree):
    leaves = jax.tree_util.tree_leaves(tree)
    return [int(np.prod(l.shape)) for l in leaves]


def flatten_tree(tree):
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.concatenate([l.reshape(-1).astype(jnp.float32)
                            for l in leaves])


def unflatten_like(flat, tree, dtype_from_tree=True):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out, off = [], 0
    for l in leaves:
        n = int(np.prod(l.shape))
        piece = flat[off:off + n].reshape(l.shape)
        out.append(piece.astype(l.dtype) if dtype_from_tree else piece)
        off += n
    return treedef.unflatten(out)


def padded_len(tree, dp: int) -> int:
    n = sum(_tree_sizes(tree))
    return ((n + dp - 1) // dp) * dp


def init_explicit_state(model, key, dp: int) -> ExplicitTrainState:
    params = model.init(key)
    plen = padded_len(params, dp)
    flat = flatten_tree(params)
    flat = jnp.pad(flat, (0, plen - flat.size))
    # host-side: full flat vector; jit in_shardings scatter it over "data"
    return ExplicitTrainState(
        params=params,
        opt=FlatAdamState(step=jnp.zeros((), jnp.int32),
                          m=jnp.zeros((plen,), jnp.float32),
                          v=jnp.zeros((plen,), jnp.float32),
                          master=flat))


def make_explicit_train_step(model, mesh_cfg: MeshConfig, tcfg: TrainConfig,
                             mesh: jax.sharding.Mesh):
    cfg = model.cfg
    lr_fn = cosine_schedule(tcfg.learning_rate, tcfg.warmup_steps,
                            tcfg.total_steps)
    proc_axes = tuple(mesh_cfg.process_axes)
    thread_axes = tuple(mesh_cfg.batch_axes)
    dp_axes = proc_axes + thread_axes

    # the root communicator over the unified DP rank space; thread_comm /
    # process_comm are the load-bearing derived sub-comms of the two-level
    # schedule. Service-mode activation: the trainer IS the parallel region.
    comm = threadcomm_init(mesh, process_axes=proc_axes,
                           thread_axes=thread_axes)
    comm.start()
    tcomm = comm.thread_comm()
    pcomm = comm.process_comm()
    dp = comm.size
    m_thread = comm.threads_per_process
    wire = (jnp.bfloat16 if tcfg.grad_comm_dtype == "bfloat16" else None)

    def inner(state: ExplicitTrainState, batch):
        (loss, metrics), grads = jax.value_and_grad(
            model.train_loss, has_aux=True)(state.params, batch)
        flat_g = flatten_tree(grads)
        plen = state.opt.master.size * m_thread  # global padded length
        flat_g = jnp.pad(flat_g, (0, plen - flat_g.size))

        opt = state.opt
        step = opt.step + 1

        if tcfg.grad_sync == "flat":
            # rank-unaware: full bytes cross every domain, then local slice
            full = comm.allreduce(flat_g) / dp
            rank = tcomm.local_rank()
            shard_len = plen // m_thread
            g_shard = lax.dynamic_slice_in_dim(full, rank * shard_len,
                                               shard_len)
        else:  # "threadcomm": hierarchical two-level via derived sub-comms
            g_shard = (tcomm.reduce_scatter(flat_g)
                       if tcomm.size > 1 else flat_g)
            if pcomm.size > 1:
                # nonblocking slow-domain sync on the "grad" stream; the
                # wire dtype compresses inter-pod bytes (level-1 gradient
                # compression). Only this stream orders against the
                # request — everything between issue and wait() may
                # overlap the inter-pod transfer.
                with comm.stream("grad"):
                    req = pcomm.iallreduce(g_shard, wire_dtype=wire)
                g_shard = req.wait()
            g_shard = g_shard / dp

        # global grad-norm from shards (for clipping)
        gn2 = jnp.sum(jnp.square(g_shard))
        if tcomm.size > 1:
            gn2 = tcomm.allreduce(gn2)
        gnorm = jnp.sqrt(gn2)
        scale = jnp.where(tcfg.grad_clip > 0,
                          jnp.minimum(1.0, tcfg.grad_clip / (gnorm + 1e-9)),
                          1.0)

        # fused flat AdamW on the shard (ZeRO-1)
        t = step.astype(jnp.float32)
        g = g_shard * scale
        m = tcfg.beta1 * opt.m + (1 - tcfg.beta1) * g
        v = tcfg.beta2 * opt.v + (1 - tcfg.beta2) * jnp.square(g)
        mhat = m / (1 - tcfg.beta1 ** t)
        vhat = v / (1 - tcfg.beta2 ** t)
        lr = lr_fn(opt.step)
        new_master = opt.master - lr * (
            mhat / (jnp.sqrt(vhat) + tcfg.eps)
            + tcfg.weight_decay * opt.master)

        # fast-domain allgather of the UPDATED parameters (cast first: move
        # bf16, not f32 — half the intra-pod bytes)
        cast = new_master.astype(
            jax.tree_util.tree_leaves(state.params)[0].dtype)
        full_new = (tcomm.allgather(cast, tiled=True)
                    if tcomm.size > 1 else cast)
        new_params = unflatten_like(full_new.astype(jnp.float32),
                                    state.params)

        metrics = {**metrics, "grad_norm": gnorm, "lr": lr}
        metrics = jax.tree_util.tree_map(
            lambda x: comm.allreduce(x) / dp, metrics)
        new_state = ExplicitTrainState(
            params=new_params,
            opt=FlatAdamState(step=step, m=m, v=v, master=new_master))
        return new_state, metrics

    # manual over the unified DP rank space; "model" stays auto (TP)
    shard_spec = P(thread_axes) if thread_axes else P()
    state_in_specs = ExplicitTrainState(
        params=jax.tree_util.tree_map(lambda _: P(), model_params_struct(model)),
        opt=FlatAdamState(step=P(), m=shard_spec, v=shard_spec,
                          master=shard_spec))
    mapped = shard_map(
        inner, mesh=mesh, axis_names=set(dp_axes),
        in_specs=(state_in_specs, P(dp_axes)),
        out_specs=(state_in_specs, P()), check_vma=False)

    # jit-level shardings: TP over "model" via the (FSDP-free) param rules
    tp_mesh_cfg = dataclasses.replace(mesh_cfg, batch_axes=())
    sample = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tp_specs = param_pspecs(cfg, tp_mesh_cfg, sample)
    st_shard = ExplicitTrainState(
        params=named_sharding(mesh, tp_specs),
        opt=FlatAdamState(
            step=NamedSharding(mesh, P()),
            m=NamedSharding(mesh, shard_spec),
            v=NamedSharding(mesh, shard_spec),
            master=NamedSharding(mesh, shard_spec)))
    b_shard = NamedSharding(mesh, batch_pspec(mesh_cfg))
    return jax.jit(mapped, in_shardings=(st_shard, b_shard),
                   out_shardings=(st_shard, None), donate_argnums=(0,))


def model_params_struct(model):
    return jax.eval_shape(model.init, jax.random.PRNGKey(0))
