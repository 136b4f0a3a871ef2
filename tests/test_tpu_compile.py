"""Compile the main path for a described TPU v5e, with no chip attached.

Each test lowers a Pallas kernel, or gemma-2b's paged decode step, at the
published widths and compiles it for one chip of a ``v5e:2x2`` topology:
what the TPU compiler refuses (unaligned blocks, unsupported primitives,
a program that does not fit HBM) fails here, while interpret-mode tests
on the CPU would pass. Nothing runs, so nothing here is a result or a
time. The topology is described inside a fixture, never at import: only
one process at a time may load the TPU library.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention.flash_attention import flash_attention_fwd
from repro.kernels.msgq import msgq
from repro.kernels.paged_attention.paged_attention import paged_attention_fwd
from repro.kernels.ssd_scan.ssd_scan import ssd_scan_fwd

V5E_HBM_BYTES = 16 * 1024 ** 3
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


# gemma-2b decode widths: H=8 query heads, one kv head, head_dim 256,
# 16-token blocks, 64 table entries per row, 8 rows
_PAGED = [((512, 16, 1, 256), BF16), ((512, 16, 1, 256), BF16),
          ((8, 64), I32), ((8,), I32)]


@pytest.mark.parametrize("q_shape", [(8, 8, 256), (8, 4, 8, 256)],
                         ids=["k1", "k4"])
def test_paged_attention_compiles(one_chip, q_shape):
    c = _compile(paged_attention_fwd, one_chip, (q_shape, BF16), *_PAGED)
    assert "tpu_custom_call" in c.as_text()


def test_flash_attention_compiles(one_chip):
    c = _compile(flash_attention_fwd, one_chip, ((1, 8, 2048, 256), BF16),
                 ((1, 1, 2048, 256), BF16), ((1, 1, 2048, 256), BF16))
    assert "tpu_custom_call" in c.as_text()


def test_ssd_scan_compiles(one_chip):
    # mamba2-370m widths: 32 heads of 64, state 128, 256-token chunks
    c = _compile(functools.partial(ssd_scan_fwd, chunk=256), one_chip,
                 ((1, 32, 2048, 64), BF16), ((1, 32, 2048), F32),
                 ((32,), F32), ((1, 2048, 128), BF16),
                 ((1, 2048, 128), BF16))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("copy,n", [
    (functools.partial(msgq.eager_copy, cell_elems=1024), 1 << 16),
    (functools.partial(msgq.one_copy, block_elems=65536), 1 << 20),
], ids=["eager", "one_copy"])
def test_msgq_compiles(one_chip, copy, n):
    c = _compile(copy, one_chip, ((n,), F32))
    assert "tpu_custom_call" in c.as_text()


def test_gemma_2b_paged_decode_step_fits_one_chip(one_chip):
    """The served decode dispatch of full-width gemma-2b in bf16 compiles
    for one chip, and its parameters, pool and temporaries fit its HBM."""
    from repro.launch.serve import serving_model
    _, _, model = serving_model("gemma-2b", smoke=False)
    rows, blocks, block_size, per_row = 8, 512, 16, 64

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = on_chip(jax.eval_shape(functools.partial(
        model.init_paged_cache, blocks, block_size, num_rows=rows)))
    step = jax.jit(model.decode_step_paged, donate_argnums=(1,))
    c = step.lower(params, pool,
                   jax.ShapeDtypeStruct((rows, 1), I32, sharding=one_chip),
                   jax.ShapeDtypeStruct((rows,), I32, sharding=one_chip),
                   jax.ShapeDtypeStruct((rows, per_row), I32,
                                        sharding=one_chip)).compile()
    mem = c.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 4.5e9        # 2.5 B bf16 params
    assert used < V5E_HBM_BYTES, used
