"""Whether what the timed path served is correct.

After the window, a sample of the requests it finished, drawn from the
seed and always holding the one with the most served tokens, is run
through the plain reference (the ``logits`` of the configuration's
architecture module, ``models/<model_type>.py``) over its prompt and its
served tokens (teacher-forced). Each request of the sample gives its last
``check.per_request`` served tokens (the deepest context it decoded at),
so that the sample's ``check.served_tokens`` come from several requests,
and so from several of the engine's rows. At each of those tokens the
*logit gap* is how far the reference's logit of that token lies below the
reference's best. A cell compares the widest or the mean gap over the
sample (its ``check.statistic``, with its ``check.limit``): a greedy
server that computes the model right serves tokens at or next to the
reference's argmax, one that computes it wrong does not. PERF.md gives
the readings each limit was set from.

The control reads the same gap for the token that the reference in a
lower precision (``quant``) puts first at each of those positions.
"""

from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _gaps(ref, tokens):
    """Gap of ``tokens`` (n,) under reference logits ``ref`` (n, V)."""
    best = jnp.max(ref, axis=-1)
    return best - jnp.take_along_axis(ref, tokens[:, None], axis=1)[:, 0]


@jax.jit
def _control_gaps(ref, ctl):
    return _gaps(ref, jnp.argmax(ctl, axis=-1).astype(jnp.int32))


def sample(finished: Sequence, seed: int, rule: dict) -> List:
    """Finished requests to check: the one with the most served tokens,
    then others in an order drawn from the seed, until the sample holds
    ``rule["served_tokens"]`` checked tokens (at most
    ``rule["per_request"]`` from each) or every finished request."""
    if not finished:
        return []
    want, cap = int(rule["served_tokens"]), int(rule["per_request"])
    ranked = sorted(finished, key=lambda r: (-len(r.served), r.rid))
    rest = list(ranked[1:])
    order = np.random.default_rng([int(seed) & (2 ** 64 - 1), 3]
                                  ).permutation(len(rest))
    picked = [ranked[0]]
    total = min(len(ranked[0].served), cap)
    for i in order:
        if total >= want:
            break
        picked.append(rest[i])
        total += min(len(rest[i].served), cap)
    return picked


def checked_tokens(picked: Sequence, rule: dict) -> int:
    return sum(min(len(r.served), int(rule["per_request"])) for r in picked)


def gaps(reference, config: dict, weights, prompt: np.ndarray,
         served: np.ndarray, last: int, quant: str | None = None
         ) -> np.ndarray:
    """For each of the ``last`` final served tokens: the gap of the served
    token (``quant`` None), or of the token the ``quant`` reference puts
    first (the control). ``reference`` is the architecture's
    ``logits``."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    n = min(last, len(served))
    first = len(seq) - n
    ref = reference(config, weights, seq, first)
    tok = np.zeros((ref.shape[0],), np.int32)
    tok[:n] = served[len(served) - n:]
    if quant is None:
        out = _gaps(ref, jnp.asarray(tok))
    else:
        ctl = reference(config, weights, seq, first, quant=quant)
        out = _control_gaps(ref, ctl)
        del ctl
    del ref
    return np.asarray(out)[:n]


def gap_stats(reference, config: dict, weights, picked: Sequence,
              rule: dict, quant: str | None = None) -> dict:
    """The widest and the mean gap over every checked token of
    ``picked``."""
    g = np.concatenate([gaps(reference, config, weights, r.prompt, r.served,
                             int(rule["per_request"]), quant)
                        for r in picked])
    return {"widest": float(g.max()), "mean": float(g.mean())}
