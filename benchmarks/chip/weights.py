"""Random model weights, drawn on the device from the run's seed.

One jitted call makes every leaf of the program's parameter tree in the
dtype it is served in. A leaf is drawn a slice of its leading axis at a
time (``lax.map``), so the 32-bit random bits behind it never exceed one
slice: peak memory at set-up stays near the weights themselves.

Values are uniform with the initialiser's standard deviation (fan-in
``1/sqrt(in)`` for projections, 0.02 for the embedding); norm weights
are ``1 +/- 0.1`` so that a norm applied twice, or not at all, shows in
the logits.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

#: elements of one drawn slice; bounds the uint32 transient to 1 GiB
_SLICE_ELEMS = 1 << 28


def seed_key(seed: int):
    """A PRNG key for any whole-number seed, also past 32 bits."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _fan_in(path: str, shape) -> int:
    """Input width of a projection leaf, by the program's layouts:
    wq/wk/wv (.., d, H, hd); wo (.., H, hd, d); mlp (.., d, f)/(.., f, d);
    experts (.., E, d, f)/(.., E, f, d); router (.., d, E);
    lm_head (d, V)."""
    name = path.rsplit("/", 1)[-1]
    if name == "wo":
        return shape[-3] * shape[-2]
    if name in ("wq", "wk", "wv"):
        return shape[-3]
    return shape[-2]


def _scale(path: str, shape) -> tuple[float, float]:
    """(centre, half-width) of the uniform a leaf is drawn from."""
    name = path.rsplit("/", 1)[-1]
    if name in ("w", "q_norm", "k_norm"):
        return 1.0, 0.1
    std = 0.02 if name == "embed" else _fan_in(path, shape) ** -0.5
    return 0.0, std * math.sqrt(3.0)


def _draw_leaf(key, shape, dtype, centre, half):
    def one(k, sub_shape):
        u = jax.random.uniform(k, sub_shape, jnp.float32, -1.0, 1.0)
        return (centre + half * u).astype(dtype)

    n = 1
    size = math.prod(shape)
    if size > _SLICE_ELEMS and len(shape) > 1:
        n = next((m for m in range(2, shape[0] + 1)
                  if shape[0] % m == 0 and size // m <= _SLICE_ELEMS),
                 shape[0])
    if n == 1:
        return one(key, shape)
    sub = (shape[0] // n,) + tuple(shape[1:])
    out = lax.map(lambda k: one(k, sub), jax.random.split(key, n))
    return out.reshape(shape)


def _paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]
    return names, [leaf for _, leaf in flat], treedef


def draw(shapes, seed: int):
    """Weights for the tree of ``jax.ShapeDtypeStruct`` ``shapes`` (as
    ``jax.eval_shape(model.init, key)`` gives them), made on the default
    device from ``seed`` in one jitted call."""
    names, leaves, treedef = _paths(shapes)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, name, leaf in zip(keys, names, leaves):
            centre, half = _scale(name, leaf.shape)
            out.append(_draw_leaf(k, tuple(leaf.shape), leaf.dtype,
                                  centre, half))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.block_until_ready(jax.jit(make)(seed_key(seed)))
