"""The chip benchmark's traffic generator: every seed gets the same sizes
and arrivals, each block the quantiles of the mix's distributions, all
clipped, in an order of the seed's; the seed draws the prompt tokens."""

import itertools
from pathlib import Path
import json

from benchmarks.chip import traffic

REPO = Path(__file__).resolve().parents[2]

MIXES = sorted((REPO / "benchmarks" / "chip" / "traffic").glob("*.json"))


def _mix(path):
    return json.loads(path.read_text())


SEED = 2 ** 31 + 5


def _take(mix, n, seed=SEED):
    return list(itertools.islice(traffic.draws(mix, seed), n))


def test_every_block_holds_the_quantiles_in_a_fixed_order():
    for path in MIXES:
        mix = _mix(path)
        n = mix["block"]
        reqs = _take(mix, 2 * n)
        assert reqs == _take(mix, 2 * n)
        prompts, outputs, gaps = traffic.block_sizes(mix)
        for blk in (reqs[:n], reqs[n:]):
            assert sorted(d.prompt_len for d in blk) == sorted(prompts)
            assert sorted(d.max_new for d in blk) == sorted(outputs)
            assert sorted(d.gap_s for d in blk) == sorted(gaps)
        assert [d.prompt_len for d in reqs[:n]] != \
            [d.prompt_len for d in reqs[n:]]
        other = traffic.draws({**mix, "order_seed": 1}, SEED)
        assert [d.prompt_len for d in itertools.islice(other, n)] != \
            [d.prompt_len for d in reqs[:n]]


def test_the_seed_orders_the_prompts_of_each_block():
    """Seeds swap neighbouring prompt quantiles: another order of the
    same sizes, each prompt at most one quantile from the mix's place."""
    for path in MIXES:
        mix = _mix(path)
        n = mix["block"]
        prompts, _, _ = traffic.block_sizes(mix)
        rank = {p: i for i, p in enumerate(prompts)}
        runs = {s: _take(mix, 4 * n, s) for s in (1, SEED, 2 ** 40 + 3)}
        orders = {tuple(d.prompt_len for d in r) for r in runs.values()}
        assert len(orders) == len(runs)
        a, b = runs[1], runs[SEED]
        assert [d.max_new for d in a] == [d.max_new for d in b]
        assert all(abs(rank[x.prompt_len] - rank[y.prompt_len]) <= 1
                   for x, y in zip(a, b))
        for r in runs.values():
            for k in range(4):
                blk = r[k * n:(k + 1) * n]
                assert sorted(d.prompt_len for d in blk) == sorted(prompts)


def test_lengths_are_clipped_and_centred():
    for path in MIXES:
        mix = _mix(path)
        for key in ("prompt", "output"):
            dist = mix[key]
            lens = [traffic.quantile(dist, u) for u in (1e-9, 0.5, 1 - 1e-9)]
            assert lens[0] == dist["min"] and lens[2] == dist["max"]
            if dist["dist"] == "lognormal":
                assert lens[1] == dist["median"]
        assert all(mix["prompt"]["min"] <= d.prompt_len <= mix["prompt"]["max"]
                   for d in _take(mix, 3 * mix["block"]))


def test_poisson_rate_is_the_mix_rate():
    mix = {"block": 64, "prompt": {"dist": "uniform", "min": 1, "max": 2},
           "output": {"dist": "uniform", "min": 1, "max": 2},
           "arrival": {"kind": "poisson", "rate": 5.0}}
    gaps = [d.gap_s for d in _take(mix, 64)]
    assert abs(len(gaps) / sum(gaps) - 5.0) < 0.15


def test_prompt_tokens_from_the_seed():
    a = traffic.prompt_tokens(2 ** 33 + 1, 7, 100, 50304)
    assert a.shape == (1, 100) and a.dtype.name == "int32"
    assert (a >= 0).all() and (a < 50304).all()
    assert (a == traffic.prompt_tokens(2 ** 33 + 1, 7, 100, 50304)).all()
    assert (a != traffic.prompt_tokens(2 ** 33 + 1, 8, 100, 50304)).any()
