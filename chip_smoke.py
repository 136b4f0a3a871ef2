"""Serve gemma-2b at its published size on TPU chips through the paged engine.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: the replicated fabric

One chip: eight requests (prompts of 16, 128 and 512 tokens, 32 new
tokens each) go through ``ContinuousEngine(kv_layout="paged")`` with
chunked prefill, driven by the serve driver's own traffic loop. Every
generated token is checked against a plain reference: the model's
cache-free full-sequence forward over prompt plus generated tokens.

Four chips: the same trace goes through
``ServingFabric(placement="replicated", ranks=4)``, rank i on chip i,
and every token must equal a single paged engine's on chip 0.

Weights are random, made from ``--seed``. Everything runs in this one
process. The script exits non-zero, printing no result, when JAX finds no
TPU or any phase fails; the last line of a passing run is
``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.cache import use_compile_cache  # noqa: E402
from repro.launch.serve import (drive_continuous, drive_fabric,  # noqa: E402
                                requests_from_trace, serving_model,
                                warm_fabric)
from repro.models import layers, transformer  # noqa: E402
from repro.models.registry import make_synthetic_batch  # noqa: E402
from repro.serve import ContinuousEngine, ServingFabric, make_trace  # noqa: E402

ARCH = "gemma-2b"
PROMPT_LENS = (16, 128, 512)
MAX_NEW = 32
ROWS = 8
PREFILL_CHUNK = 64
#: a generated token passes when its reference logit is within this many
#: bf16 ulps (at the row's largest |logit|) of the reference maximum.
#: Both sides run the bf16 model, and the lm-head output is itself a bf16
#: value: each logit is known to half an ulp, and the engine's hidden
#: state differs from the reference's by bf16 reassociation (paged block
#: gather vs full attention, 64-row chunks vs the whole prompt), which
#: moves a logit by about an ulp. Random-init logits have many near-ties
#: at the top, so the exact argmax can flip between the two; a wrong
#: position or a corrupt cache instead lands several logit std devs below.
MARGIN_ULPS = 4.0


def reference_logits(model, params, tokens, first: int):
    """Cache-free full-sequence forward of ``tokens`` (1, T): the bf16
    logits at positions ``first`` .. T-1, over the real vocabulary."""
    cfg = model.cfg
    cdt = layers.dtype_of(model.knobs["compute_dtype"])
    x = transformer.embed_tokens(cfg, params, tokens, cdt)
    hidden, _, _ = transformer.backbone(
        cfg, params, x, jnp.arange(tokens.shape[1]), model.knobs,
        remat=False)
    w = transformer.lm_head_weight(cfg, params).astype(cdt)
    return (hidden[0, first:] @ w).astype(jnp.float32)[:, :cfg.vocab_size]


def check_against_reference(model, params, reqs) -> dict:
    """Every generated token against the reference forward, teacher-forced
    on the engine's own tokens. Raises on a token outside the margin."""
    ref = jax.jit(functools.partial(reference_logits, model),
                  static_argnums=(2,))
    exact = total = 0
    worst = 0.0
    for r in reqs:
        prompt = np.asarray(r.batch["tokens"][0])
        gen = r.output[:r.generated]
        seq = np.concatenate([prompt, gen[:-1]])[None]
        logits = np.asarray(ref(params, jnp.asarray(seq), len(prompt) - 1))
        top = logits.max(axis=1)
        scale = np.maximum(np.abs(logits).max(axis=1), 1e-30)
        ulp = 2.0 ** (np.floor(np.log2(scale)) - 7)     # bf16: 8-bit mantissa
        deficit = (top - logits[np.arange(gen.size), gen]) / ulp
        exact += int((logits.argmax(axis=1) == gen).sum())
        total += int(gen.size)
        worst = max(worst, float(deficit.max()))
        bad = np.flatnonzero(deficit > MARGIN_ULPS)
        if bad.size:
            raise AssertionError(
                f"request {r.rid}: generated token {int(bad[0])} is "
                f"{float(deficit[bad[0]]):.2f} bf16 ulps below the "
                f"reference argmax (margin {MARGIN_ULPS})")
    return {"tokens_checked": total, "exact_argmax": exact,
            "worst_deficit_ulps": worst}


def _trace_requests(cfg, dtype, seed):
    trace = make_trace(ROWS, prompt_len=PROMPT_LENS, max_new=MAX_NEW,
                       arrival="all", seed=seed)
    return requests_from_trace(cfg, trace, dtype=dtype, seed=seed)


def _check_complete(reqs) -> int:
    for r in reqs:
        if r.output is None or r.generated != r.max_new_tokens:
            raise AssertionError(
                f"request {r.rid} came back with {r.generated} of "
                f"{r.max_new_tokens} tokens")
    return sum(r.generated for r in reqs)


def _paged_engine(model, params):
    return ContinuousEngine(
        model, params, cache_len=max(PROMPT_LENS) + MAX_NEW, num_slots=ROWS,
        prefill_chunk=PREFILL_CHUNK, max_prefill_per_step=2,
        kv_layout="paged")


def _warm(eng, cfg, dtype, seed) -> float:
    """Compile the engine's dispatches on two short prompts, then reset it.
    Returns the seconds it took (compilation, reported as set-up)."""
    t0 = time.perf_counter()
    warm = make_synthetic_batch(cfg, 2, PROMPT_LENS[0], seed=seed,
                                compute_dtype=dtype)
    eng.generate({"tokens": np.asarray(warm["tokens"])}, 2)
    eng.reset()
    return time.perf_counter() - t0


@contextlib.contextmanager
def _no_compiles(what: str):
    """Raise if JAX compiled (or fetched from its cache) any program
    inside the block: a measured drive must run on warmed programs."""
    seen = []

    def listener(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    if seen:
        raise AssertionError(f"{len(seen)} program(s) compiled inside the "
                             f"measured {what} ({sum(seen):.1f} s)")


def _memory(device) -> dict:
    stats = device.memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}


def serve_one_chip(*, smoke: bool = False, seed: int = 0) -> dict:
    """The paged continuous engine over the trace, checked token by token
    against the reference forward."""
    cfg, dtype, model = serving_model(ARCH, smoke=smoke)
    t0 = time.perf_counter()
    params = jax.block_until_ready(model.init(jax.random.PRNGKey(seed)))
    init_s = time.perf_counter() - t0
    eng = _paged_engine(model, params)
    compile_s = _warm(eng, cfg, dtype, seed)
    reqs = _trace_requests(cfg, dtype, seed)
    with _no_compiles("engine drive"):
        stats = drive_continuous(eng, reqs)
    tokens = _check_complete(reqs)
    t0 = time.perf_counter()
    agree = check_against_reference(model, params, reqs)
    return {"arch": cfg.name, "layers": cfg.num_layers,
            "d_model": cfg.d_model, "vocab": cfg.vocab_size,
            "dtype": dtype, "init_s": init_s, "compile_s": compile_s,
            "wall_s": stats["makespan_s"], "tok_s": stats["tok_s"],
            "tokens": tokens, "requests": len(reqs),
            "reference_s": time.perf_counter() - t0, **agree,
            "memory": _memory(jax.devices()[0])}


def serve_four_chips(*, smoke: bool = False, seed: int = 0) -> dict:
    """The replicated fabric, one rank per chip, against one paged engine
    on chip 0: every request answered, every token equal."""
    cfg, dtype, model = serving_model(ARCH, smoke=smoke)
    params = jax.block_until_ready(model.init(jax.random.PRNGKey(seed)))

    eng = _paged_engine(model, params)
    single_compile_s = _warm(eng, cfg, dtype, seed)
    single = _trace_requests(cfg, dtype, seed)
    with _no_compiles("engine drive"):
        single_stats = drive_continuous(eng, single)
    _check_complete(single)
    del eng

    fab = ServingFabric(model, params, ranks=4, placement="replicated",
                        cache_len=max(PROMPT_LENS) + MAX_NEW,
                        slots_per_rank=ROWS, prefill_chunk=PREFILL_CHUNK,
                        max_prefill_per_step=2)
    try:
        t0 = time.perf_counter()
        # the whole prompt mix: each rank then meets chunk and decode
        # dispatches in every order the drive will
        warm_fabric(fab, cfg, dtype=dtype, seed=seed,
                    prompt_len=PROMPT_LENS)
        fabric_compile_s = time.perf_counter() - t0
        reqs = _trace_requests(cfg, dtype, seed)
        with _no_compiles("fabric drive"):
            stats = drive_fabric(fab, reqs)
        tokens = _check_complete(reqs)
        same = sum(int(np.array_equal(a.output[:a.generated],
                                      b.output[:b.generated]))
                   for a, b in zip(single, reqs))
        ranks_info = [{"rank": w.rank, "device": str(w.device),
                       "requests": w.n_finished, "tokens": w.tokens_out,
                       "memory": _memory(w.device)} for w in fab.workers]
    finally:
        fab.close()
    devices = {r["device"] for r in ranks_info}
    if len(devices) != len(ranks_info):
        raise AssertionError(f"{len(ranks_info)} ranks ran on "
                             f"{len(devices)} device(s): {sorted(devices)}")
    if same != len(reqs):
        raise AssertionError(f"only {same} of {len(reqs)} requests match "
                             "the single engine token for token")
    return {"arch": cfg.name, "ranks": len(ranks_info), "requests": len(reqs),
            "tokens": tokens, "identical_requests": same,
            "single_compile_s": single_compile_s,
            "single_wall_s": single_stats["makespan_s"],
            "fabric_compile_s": fabric_compile_s,
            "fabric_wall_s": stats["makespan_s"], "per_rank": ranks_info}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the paged engine against the reference "
                         "forward; 4: the replicated fabric, one rank per "
                         "chip, against the paged engine")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    print(f"device: {dev.device_kind} x{len(devices)}", flush=True)
    if args.chips == 1:
        result = serve_one_chip(seed=args.seed)
    else:
        result = serve_four_chips(seed=args.seed)
    for key, value in result.items():
        print(f"{key}: {value}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
