"""Compile a cell's engine programs for a described TPU v5e chip, with no
chip attached, and print what each would hold in device memory.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/aot.py <workload> [...]

For each workload: the weights (from the configuration's shapes), the
paged pool its cell file asks for, and the chunk and decode programs the
engine dispatches, lowered at the cell's shapes with the pool and the
decode state donated as the engine donates them. The compiler refuses a
program that does not fit; ``memory_analysis()`` gives its argument,
output and temporary bytes.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


def _nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


def rehearse(workload: str, device) -> dict:
    from benchmarks.chip import drive, spec
    from repro.serve import ContinuousEngine

    cell = spec.cell(workload)
    s = cell.settings["serving"]
    bs = int(cell.config["block_size"])
    S, P, C = int(s["rows"]), int(s["prefill_rows"]), int(s["prefill_chunk"])
    mbr = -(-int(s["cache_len"]) // bs)
    model = drive.program_model(cell.arch.program_config(cell.config))
    on = SingleDeviceSharding(device)

    def place(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on),
            tree)

    def spec_of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on)

    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    buf = place(jax.eval_shape(
        lambda: model.init_paged_cache(int(s["pool_tokens"]) // bs, bs,
                                       num_rows=S)))
    state = {"tok": spec_of((S, 1, 1), jnp.int32),
             "pos": spec_of((S,), jnp.int32),
             "keys": spec_of((S, 2), jnp.uint32),
             "temp": spec_of((S,), jnp.float32)}
    i32 = jnp.int32
    decode = jax.jit(ContinuousEngine._decode_impl_paged(model),
                     donate_argnums=(1, 2)).lower(
        params, buf, state, spec_of((S, mbr), i32)).compile()
    chunk = jax.jit(ContinuousEngine._chunk_impl_paged(model, S),
                    donate_argnums=(1, 2)).lower(
        params, buf, state, spec_of((P, C), i32), spec_of((P,), i32),
        spec_of((P, mbr), i32), spec_of((P,), i32), spec_of((P,), i32),
        spec_of((P,), i32), spec_of((P, 2), jnp.uint32),
        spec_of((P,), jnp.float32)).compile()
    out = {"weights_bytes": _nbytes(params), "pool_bytes": _nbytes(buf)}
    for name, prog in (("decode", decode), ("chunk", chunk)):
        m = prog.memory_analysis()
        out[name] = {"argument": m.argument_size_in_bytes,
                     "output": m.output_size_in_bytes,
                     "alias": m.alias_size_in_bytes,
                     "temp": m.temp_size_in_bytes}
    return out


def main(argv) -> int:
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for workload in argv:
        r = rehearse(workload, topo.devices[0])
        peak = (r["weights_bytes"] + r["pool_bytes"]
                + max(r["decode"]["temp"], r["chunk"]["temp"]))
        print(workload, r, f"weights+pool+largest temp = {peak / 1e9:.3f} GB",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
