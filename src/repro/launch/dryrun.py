import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

# ^ MUST precede any jax import: jax locks the device count on first init.
# The 512 placeholder host devices exist ONLY for the dry-run; tests and
# benchmarks run with the real single CPU device.

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.config import (SHAPES, ServeConfig, TrainConfig,  # noqa: E402
                          shape_applicable)
from repro.configs import ARCH_NAMES, get_config, get_smoke_config  # noqa: E402
from repro.dist.sharding import (batch_pspec, cache_pspecs,  # noqa: E402
                                 named_sharding, param_pspecs)
from repro.launch.mesh import (make_mesh_from_config,  # noqa: E402
                               production_mesh_config)
from repro.models.registry import batch_spec, build_model  # noqa: E402
from repro.roofline.analysis import analyze_compiled  # noqa: E402
from repro.train.trainer import init_train_state, make_train_step  # noqa: E402

ARTIFACT_DIR = os.environ.get(
    "REPRO_ARTIFACT_DIR",
    os.path.join(os.path.dirname(__file__), "..", "..", "..",
                 "experiments", "artifacts"))


def _strip_batch_axes(spec_tree, batch_dims):
    """Replace the batch-dim axis with None (for shapes whose global batch
    does not divide the dp degree, e.g. long_500k's batch=1)."""
    def fix(spec):
        parts = list(spec)
        for i in batch_dims:
            if i < len(parts):
                parts[i] = None
        return P(*parts)
    return jax.tree_util.tree_map(
        fix, spec_tree, is_leaf=lambda s: isinstance(s, P))


def build_cell(arch: str, shape_name: str, mesh_name: str, *,
               smoke: bool = False, grad_sync: str = "spmd",
               act_mode: str = "sp", shard_mode: str = "2d",
               extra_train_kwargs=None):
    """Return (jitted_fn, arg_shapestructs, meta) for one dry-run cell.

    act_mode: residual-stream constraint at block boundaries —
      "batch": batch-sharded only (naive; remat-saved stacks replicate over
               the model axis → 79GB/device on yi-9b, does not fit),
      "sp":    + sequence dim sharded over "model" (Megatron sequence
               parallelism; saved activations shrink tp×). See §Perf.
    """
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return None, None, {"skipped": why}
    mesh_cfg = production_mesh_config(multi_pod=(mesh_name == "multi_pod"))
    if shard_mode == "dp_only":
        # small-model policy: no TP/FSDP, batch over ALL axes, weights
        # replicated — kills the weight-gather/TP-psum collective floor
        # that dominates tiny models over-sharded on 256+ chips (§Perf)
        mesh_cfg = dataclasses.replace(
            mesh_cfg,
            batch_axes=tuple(mesh_cfg.batch_axes) + tuple(mesh_cfg.model_axes),
            model_axes=())
    mesh = make_mesh_from_config(mesh_cfg)
    tp = mesh_cfg.tp
    dp = mesh_cfg.dp

    ring = shape.name == "long_500k"
    tkw = dict(param_dtype="bfloat16", compute_dtype="bfloat16",
               remat=True, grad_sync=grad_sync, loss_chunk=512,
               attn_chunk_threshold=2048, attn_chunk=512)
    if shape.kind == "train":
        # microbatch count: keep the remat-saved residual stack (the
        # dominant live activation, ~tokens_sp × d × L × 2B per device)
        # under ~1GB — calibrated on the measured yi-9b cell (§Perf)
        seq_sp = tp if (act_mode == "sp" and shape.seq_len % tp == 0) else 1
        tokens_dev = shape.global_batch * shape.seq_len / dp / seq_sp
        saved = tokens_dev * cfg.d_model * cfg.num_layers * 2
        mb = 1
        while (saved / mb > 0.5e9 and mb < 16
               and shape.global_batch % (2 * mb) == 0
               and (shape.global_batch // (2 * mb)) % dp == 0):
            mb *= 2
        tkw["microbatches"] = mb
        if cfg.d_model >= 6144:
            tkw["loss_chunk"] = 256   # bound CE logits temp on giant d/vocab
        # large kv blocks in the chunked-attention backward: carries scale
        # as S²/chunk_kv per layer (§Perf iteration 4)
        tkw["attn_chunk_kv"] = 2048
    if shard_mode == "dp_only":
        tkw["fsdp"] = False
    tkw.update(extra_train_kwargs or {})
    tcfg = TrainConfig(**tkw)
    scfg = ServeConfig(ring_buffer=ring)
    batch_div = shape.global_batch % dp == 0
    tp_axis = mesh_cfg.model_axes[0] if mesh_cfg.model_axes else None
    explicit = grad_sync != "spmd"
    if explicit:
        # the explicit threadcomm trainer runs fwd/bwd inside a shard_map
        # whose (pod, data) axes are MANUAL: constraints may only mention
        # auto axes, and jax.checkpoint-inside-manual-shard_map currently
        # miscompiles the SSD cumsum — measure collectives w/o remat
        tkw_update = {"remat": False, "microbatches": 1}
        tcfg = dataclasses.replace(tcfg, **tkw_update)
    # batch dim of activation constraints: only in auto (spmd) mode
    b_ax = None
    from repro.dist.sharding import batch_axes as _baxes
    if not explicit:
        b_ax = _baxes(mesh_cfg)
    act_sharding = None
    if batch_div and shape.kind == "train":
        seq_axis = (tp_axis if tp_axis and act_mode == "sp"
                    and shape.seq_len % mesh_cfg.axis_size(tp_axis) == 0
                    else None)
        act_sharding = NamedSharding(mesh, P(b_ax, seq_axis, None))
    elif batch_div:
        act_sharding = NamedSharding(mesh, P(b_ax, None, None))
    attn_sharding = None
    if batch_div and tp_axis and cfg.num_heads and cfg.num_heads % tp == 0:
        attn_sharding = NamedSharding(mesh, P(b_ax, None, tp_axis, None))
    model = build_model(cfg, tcfg, scfg, tp=tp, act_sharding=act_sharding,
                        attn_sharding=attn_sharding)

    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_specs = param_pspecs(cfg, mesh_cfg, params_sds)
    p_shard = named_sharding(mesh, p_specs)
    batch_divisible = shape.global_batch % dp == 0
    b_pspec = (batch_pspec(mesh_cfg) if batch_divisible else P())
    b_shard = NamedSharding(mesh, b_pspec)
    bspec = batch_spec(cfg, shape, tcfg.compute_dtype)

    meta = {
        "arch": cfg.name, "shape": shape.name, "mesh": mesh_name,
        "kind": shape.kind, "devices": mesh_cfg.num_devices,
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
        "global_batch": shape.global_batch, "seq_len": shape.seq_len,
        "grad_sync": grad_sync,
    }

    if shape.kind == "train":
        step = make_train_step(model, mesh_cfg, tcfg, mesh=mesh)
        if grad_sync == "spmd":
            state_sds = jax.eval_shape(
                lambda k: init_train_state(model, k), jax.random.PRNGKey(0))
        else:
            from repro.train.explicit import init_explicit_state
            state_sds = jax.eval_shape(
                lambda k: init_explicit_state(model, k, dp=dp),
                jax.random.PRNGKey(0))
        # 6 * N_active * tokens (bwd included), per device
        tokens = shape.global_batch * shape.seq_len
        meta["model_flops_per_device"] = (
            6 * cfg.active_param_count() * tokens / mesh_cfg.num_devices)
        return step, (state_sds, bspec), meta

    from repro.models.registry import cache_len_for
    cache_len = cache_len_for(cfg, shape, scfg)
    meta["cache_len"] = cache_len

    if shape.kind == "prefill":
        cache_sds = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, cache_len))
        c_specs = cache_pspecs(cfg, mesh_cfg, cache_sds)
        if not batch_divisible:
            c_specs = _strip_batch_axes(c_specs, (1,))
        fn = jax.jit(lambda p, b: model.prefill(p, b, cache_len),
                     in_shardings=(p_shard, b_shard),
                     out_shardings=(NamedSharding(mesh, P()),
                                    named_sharding(mesh, c_specs)))
        tokens = shape.global_batch * shape.seq_len
        meta["model_flops_per_device"] = (
            2 * cfg.active_param_count() * tokens / mesh_cfg.num_devices)
        return fn, (params_sds, bspec), meta

    # decode: one new token against a seq_len cache
    cache_sds = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, cache_len))
    c_specs = cache_pspecs(cfg, mesh_cfg, cache_sds)
    if not batch_divisible:
        c_specs = _strip_batch_axes(c_specs, (1,))
    c_shard = named_sharding(mesh, c_specs)
    tok_sds = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
    fn = jax.jit(model.decode_step,
                 in_shardings=(p_shard, c_shard, b_shard, None),
                 out_shardings=(NamedSharding(mesh, P()), c_shard),
                 donate_argnums=(1,))
    meta["model_flops_per_device"] = (
        2 * cfg.active_param_count() * shape.global_batch
        / mesh_cfg.num_devices)
    return fn, (params_sds, cache_sds, tok_sds, pos_sds), meta


def run_cell(arch: str, shape_name: str, mesh_name: str, *,
             smoke=False, grad_sync="spmd", shard_mode="2d", verbose=True,
             extra_train_kwargs=None):
    fn, args, meta = build_cell(arch, shape_name, mesh_name, smoke=smoke,
                                grad_sync=grad_sync, shard_mode=shard_mode,
                                extra_train_kwargs=extra_train_kwargs)
    meta = dict(meta, shard_mode=shard_mode)
    if fn is None:
        return {"meta": meta}
    t0 = time.time()
    lowered = fn.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    if verbose:
        print(compiled.memory_analysis())   # proves it fits
        cost = compiled.cost_analysis() or {}
        print({k: v for k, v in cost.items()
               if k in ("flops", "bytes accessed")})

    # analytical compute/memory terms (HLO cost_analysis counts scan bodies
    # once — see roofline/analysis.py docstring)
    from repro.roofline.flops import cell_compute_flops, cell_memory_bytes
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    shape = SHAPES[shape_name]
    mesh_cfg = production_mesh_config(multi_pod=(mesh_name == "multi_pod"))
    comp = cell_compute_flops(cfg, shape)
    memb = cell_memory_bytes(cfg, shape, mesh_cfg,
                             cache_len=meta.get("cache_len"))
    analytic = {
        "computed_flops_per_device": comp["computed"] / mesh_cfg.num_devices,
        "bytes_per_device": memb["bytes"],
        "flops_breakdown": comp, "bytes_breakdown": memb,
    }
    analysis = analyze_compiled(
        compiled, model_flops=meta.get("model_flops_per_device"),
        analytic=analytic)
    return {"meta": meta, "analysis": analysis,
            "timings": {"lower_s": t_lower, "compile_s": t_compile}}


def artifact_path(arch, shape_name, mesh_name, grad_sync="spmd",
                  shard_mode="2d"):
    tag = "" if grad_sync == "spmd" else f"__{grad_sync}"
    if shard_mode != "2d":
        tag += f"__{shard_mode}"
    d = os.path.join(ARTIFACT_DIR, mesh_name)
    return os.path.join(d, f"{arch}__{shape_name}{tag}.json")


def all_cells():
    for arch in ARCH_NAMES:
        for shape_name in ("train_4k", "prefill_32k", "decode_32k",
                           "long_500k"):
            yield arch, shape_name


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single_pod",
                    choices=["single_pod", "multi_pod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--grad-sync", default="spmd",
                    choices=["spmd", "threadcomm", "flat"])
    ap.add_argument("--shard-mode", default="2d", choices=["2d", "dp_only"])
    args = ap.parse_args()

    meshes = (["single_pod", "multi_pod"] if args.mesh == "both"
              else [args.mesh])
    cells = (list(all_cells()) if args.all
             else [(args.arch, args.shape)])
    n_ok = n_skip = n_fail = 0
    for mesh_name in meshes:
        for arch, shape_name in cells:
            path = artifact_path(arch, shape_name, mesh_name, args.grad_sync,
                                 args.shard_mode)
            if os.path.exists(path) and not args.force:
                print(f"[cached] {mesh_name}/{arch}/{shape_name}")
                n_ok += 1
                continue
            print(f"=== {mesh_name} :: {arch} :: {shape_name} "
                  f"(grad_sync={args.grad_sync}) ===", flush=True)
            try:
                res = run_cell(arch, shape_name, mesh_name, smoke=args.smoke,
                               grad_sync=args.grad_sync,
                               shard_mode=args.shard_mode)
            except Exception:
                traceback.print_exc()
                n_fail += 1
                continue
            if "analysis" not in res:
                print(f"[skip] {res['meta'].get('skipped')}")
                n_skip += 1
            else:
                terms = res["analysis"]["terms"]
                print(f"[ok] dominant={res['analysis']['dominant']} "
                      f"compute={terms['compute_s']:.4f}s "
                      f"memory={terms['memory_s']:.4f}s "
                      f"collective={terms['collective_s']:.4f}s "
                      f"fits_hbm={res['analysis']['fits_hbm']} "
                      f"(compile {res['timings']['compile_s']:.0f}s)",
                      flush=True)
                n_ok += 1
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(path, "w") as f:
                json.dump(res, f, indent=1, default=str)
    print(f"dryrun done: ok={n_ok} skip={n_skip} fail={n_fail}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
