"""The architecture modules (``benchmarks/chip/models/<model_type>.py``):
found by a configuration's ``model_type``, never by default; the Qwen3
reference bit for bit as it was before it moved into its module; and the
neutral pieces a new module builds on."""

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import drive, reference, spec, weights
from chip_bench_helpers import (TINY_CONFIGS, add_tiny_cells,
                                copy_benchmark)

#: logits of the tiny configurations, recorded from the reference as it
#: was before the move (``reference.logits``), with its tokens: weights
#: from seed 2**31 + 15, rows for positions 92-99 of 100 tokens
PINNED = Path(__file__).with_name("data") / "qwen3_tiny_logits.npz"


@pytest.mark.parametrize("name, quant", [("tiny-dense", None),
                                         ("tiny-dense", "fp8"),
                                         ("tiny-moe", None)])
def test_reference_is_pinned(name, quant):
    pinned = np.load(PINNED)
    config = {"name": name, "block_size": 16, **TINY_CONFIGS[name]}
    arch = spec.architecture(config["model_type"])
    model = drive.program_model(arch.program_config(config))
    w = drive.draw_weights(model, 2 ** 31 + 15, arch)
    tokens = pinned["tokens"]
    got = np.asarray(arch.logits(config, w, tokens, 92, quant=quant))
    want = pinned[f"{name}_{quant or 'bf16'}"]
    np.testing.assert_array_equal(got[:len(want)], want)


def test_an_unknown_model_type_names_its_module(tmp_path):
    root = copy_benchmark(tmp_path)
    add_tiny_cells(root)
    path = root / "benchmarks" / "chip" / "configs" / "tiny-dense.json"
    config = json.loads(path.read_text())
    path.write_text(json.dumps({**config, "model_type": "nosuch"}))
    with pytest.raises(FileNotFoundError, match="models/nosuch.py"):
        spec.cell("tiny-dense-batch", root)


def test_the_program_refuses_what_it_cannot_run_as_stated():
    arch = spec.architecture("olmoe")
    config = TINY_CONFIGS["tiny-moe"]
    with pytest.raises(ValueError, match="norm_topk_prob"):
        arch.program_config({**config, "config": {**config["config"],
                                                  "norm_topk_prob": False}})


@pytest.mark.parametrize("window", [None, 1, 100, 300])
def test_blocked_attention_against_one_softmax(window):
    """``reference.attention`` in blocks of queries, with and without a
    window, against the whole score matrix at once."""
    T, H, KV, hd = 512, 4, 2, 8
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(kq, (T, H, hd), jnp.float32)
    k = jax.random.normal(kk, (T, KV, hd), jnp.float32)
    v = jax.random.normal(kv, (T, KV, hd), jnp.float32)
    got = reference.attention(q, k, v, jnp.float32, window=window)
    kf, vf = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("qhk,thk->hqt", q, kf,
                   precision=reference.HIGHEST) / math.sqrt(hd)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = (j <= i) & ((j > i - window) if window else True)
    p = jax.nn.softmax(jnp.where(seen, s, reference.NEG), axis=-1)
    want = jnp.einsum("hqt,thk->qhk", p, vf, precision=reference.HIGHEST)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_a_module_draws_its_own_leaves():
    """A module's ``draw_scale`` sets the leaves it names; the rest keep
    the default rule (norm weights 1 +/- 0.1, projections by fan-in)."""
    shapes = {"attn": {"kv_norm": jax.ShapeDtypeStruct((2, 512), jnp.float32),
                       "wq": jax.ShapeDtypeStruct((64, 4, 16), jnp.float32)},
              "ln1": {"w": jax.ShapeDtypeStruct((2, 512), jnp.float32)}}

    def hook(path, shape):
        return (1.0, 0.05) if path.endswith("kv_norm") else None

    default = weights.draw(shapes, 3)
    own = weights.draw(shapes, 3, hook)
    # by default a leaf the rule does not know is centred on zero
    assert abs(float(default["attn"]["kv_norm"].mean())) < 0.1
    assert float(jnp.abs(own["attn"]["kv_norm"] - 1.0).max()) <= 0.05
    for a, b in ((default["attn"]["wq"], own["attn"]["wq"]),
                 (default["ln1"]["w"], own["ln1"]["w"])):
        np.testing.assert_array_equal(a, b)
