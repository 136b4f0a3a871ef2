"""Training launcher: config-driven driver over the trainer substrate.

On real hardware this runs the full configs over the production mesh; on
CPU use --smoke (reduced same-family configs) with a small mesh, e.g.:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
    python -m repro.launch.train --arch yi-9b --smoke --steps 50 \
      --mesh 2,2,2 --grad-sync threadcomm
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.config import MeshConfig, ServeConfig, TrainConfig
from repro.configs import ARCH_NAMES, get_config, get_smoke_config
from repro.data import SyntheticPipeline
from repro.dist.sharding import batch_pspec
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_mesh_from_config
from repro.models.registry import build_model
from repro.train import checkpoint as ckpt
from repro.train.trainer import init_train_state, make_train_step


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCH_NAMES))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="1",
                    help="comma mesh shape; 1=single device, 2,2,2=pod/data/model")
    ap.add_argument("--grad-sync", default="spmd",
                    choices=["spmd", "threadcomm", "flat"])
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    shape = tuple(int(x) for x in args.mesh.split(","))
    if shape == (1,):
        mesh_cfg = MeshConfig(shape=(1,), axis_names=("data",))
        mesh = None
    elif len(shape) == 3:
        mesh_cfg = MeshConfig(shape=shape, axis_names=("pod", "data", "model"),
                              process_axes=("pod",))
        mesh = make_mesh_from_config(mesh_cfg)
    else:
        mesh_cfg = MeshConfig(shape=shape, axis_names=("data", "model"))
        mesh = make_mesh_from_config(mesh_cfg)

    dtype = "float32" if args.smoke else "bfloat16"
    tcfg = TrainConfig(param_dtype=dtype, compute_dtype=dtype,
                       learning_rate=args.lr, warmup_steps=10,
                       total_steps=max(args.steps, 100),
                       grad_sync=args.grad_sync, remat=not args.smoke,
                       loss_chunk=min(64, args.seq),
                       attn_chunk_threshold=max(256, args.seq))
    model = build_model(cfg, tcfg, ServeConfig(), tp=mesh_cfg.tp)
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"mesh={shape} grad_sync={args.grad_sync}")

    pipe = SyntheticPipeline(cfg, batch=args.batch, seq_len=args.seq, seed=0)
    if args.grad_sync == "spmd" or mesh is None:
        state = init_train_state(model, jax.random.PRNGKey(0))
        step_fn = jax.jit(make_train_step(model, mesh_cfg, tcfg))
    else:
        from repro.train.explicit import init_explicit_state
        state = init_explicit_state(model, jax.random.PRNGKey(0),
                                    dp=mesh_cfg.dp)
        step_fn = make_train_step(model, mesh_cfg, tcfg, mesh=mesh)
    b_shard = (NamedSharding(mesh, batch_pspec(mesh_cfg))
               if mesh is not None else None)

    start = 0
    if args.resume and args.ckpt_dir and ckpt.latest_step(args.ckpt_dir):
        state, start, _ = ckpt.restore(args.ckpt_dir, state)
        print(f"resumed at step {start}")

    t0 = time.time()
    for i in range(start, args.steps):
        batch = pipe.get_batch(i)
        batch = {k: (jax.device_put(jnp.asarray(v), b_shard)
                     if b_shard else jnp.asarray(v))
                 for k, v in batch.items()}
        state, metrics = step_fn(state, batch)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:5d} loss {float(metrics['loss']):.4f} "
                  f"({time.time() - t0:.1f}s)", flush=True)
        if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
            ckpt.save(args.ckpt_dir, i + 1, state,
                      extra=pipe.state_dict(i + 1), keep=3)
    print("done.")


if __name__ == "__main__":
    main()
