"""Serving traffic driver: arrival traces through the continuous-batching
engine vs the static-batch baseline (DESIGN.md §8).

Generates a Poisson/burst arrival trace, drives one or both engines over
it in wall-clock time, and reports per-request latency percentiles plus
useful-token throughput. With ``--json`` the measurements land in
``BENCH_serve.json`` (the CI serving artifact), including a verified
static-vs-continuous comparison row and a greedy parity check.

The continuous engine runs its prompt deposits *chunked* (fixed-size
chunk rows batched across requests, interleaved with decode micro-steps)
and, for comparison, once more with monolithic prefill — the artifact
records TTFT p50/p95 for both plus prefill compile counts on a
mixed-prompt-length trace (chunked compiles are independent of the number
of distinct prompt lengths; monolithic pays one XLA compile per length).
A further comparison run swaps the slot pool for the *paged* KV substrate
(DESIGN.md §9) at the exact same HBM budget and records bytes per
resident token, peak concurrency and trace-level token identity.

With ``--fabric replicated|disagg|both --ranks N`` the driver instead
runs the multi-rank serving fabric comparison (DESIGN.md §10): the same
trace through a single paged engine and through the router-dispatched
fabric under each placement policy, recording aggregate tok/s, TTFT
percentiles per policy, per-rank utilization, KV-migration pricing and
greedy token identity (``BENCH_fabric.json``, schema
``repro-serve-bench-v8``).

CPU demo:
  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
      --engine both --requests 12 --slots 4 --prompt-len 16,256 \
      --prefill-chunk 64 --max-new-lo 4 --max-new-hi 32 \
      --json BENCH_serve.json

``benchmarks/bench_serve.py`` imports :func:`run_traffic` for the bench
harness rows; this module stays the human-facing entry point.
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ServeConfig, TrainConfig
from repro.configs import ARCH_NAMES, get_config, get_smoke_config
from repro.launch.cache import use_compile_cache
from repro.models.registry import build_model, make_synthetic_batch
from repro.obs import metrics as obs_metrics
from repro.obs import residuals as obs_residuals
from repro.obs import trace as obs_trace
from repro.serve import (ContinuousEngine, ServeRequest, ServingFabric,
                         StaticEngine, make_trace)

#: registry families the ``--config`` sweep covers by default: one per
#: serving structure (dense, MoE, SSM, hybrid, enc-dec) — every family
#: the state-threaded chunk contract (DESIGN.md §13) must carry
FAMILY_ARCHS = ("gemma-2b", "olmoe-1b-7b", "mamba2-370m", "hymba-1.5b",
                "whisper-tiny")


def serving_model(arch: str, *, smoke: bool, ring: bool = False):
    """``(cfg, dtype, model)`` as every serving run builds them: the smoke
    config in float32, or the published config in bfloat16."""
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    dtype = "float32" if smoke else "bfloat16"
    tcfg = TrainConfig(param_dtype=dtype, compute_dtype=dtype, remat=False,
                       loss_chunk=64, attn_chunk_threshold=4096)
    return cfg, dtype, build_model(cfg, tcfg, ServeConfig(ring_buffer=ring),
                                   tp=1)


def effective_chunk(caps, prefill_chunk: int) -> int:
    """Capability-aware chunk size: floor to the family's
    ``chunk_multiple`` (SSM/hybrid scans resume on the monolithic decay
    grid only at ``ssm_chunk`` boundaries), never below one multiple;
    0 (monolithic) when the family cannot chunk at all."""
    if prefill_chunk <= 0 or not caps.chunked_prefill:
        return 0
    m = max(1, int(caps.chunk_multiple))
    return max(m, (prefill_chunk // m) * m)


def useful_tokens(row: np.ndarray, eos_id: int) -> int:
    """Tokens a request actually produced: up to and including the first
    EOS (or the full row when EOS never fires / is disabled)."""
    if eos_id >= 0:
        hits = np.flatnonzero(row == eos_id)
        if hits.size:
            return int(hits[0]) + 1
    return int(row.size)


def requests_from_trace(cfg, trace, *, dtype: str = "float32",
                        seed: int = 0) -> List[ServeRequest]:
    """Materialize one ServeRequest per trace entry with a distinct
    synthetic prompt (seeded per request id).

    Entries carrying a ``prefix_group`` (shared-prefix traces — system
    prompt / few-shot template workloads) open with their group's
    template tokens: one synthetic template per group, sliced to each
    entry's ``prefix_len``; the suffix stays the entry's own random
    tokens. Deterministic in ``seed``, so two engines driven from the
    same trace see byte-identical prompts."""
    templates: Dict[int, np.ndarray] = {}
    longest: Dict[int, int] = {}
    for e in trace:
        g = getattr(e, "prefix_group", -1)
        if g >= 0 and e.prefix_len > 0:
            longest[g] = max(longest.get(g, 0), e.prefix_len)
    for g, plen in longest.items():
        tb = make_synthetic_batch(cfg, 1, plen, seed=seed + 131 + g,
                                  compute_dtype=dtype)
        templates[g] = np.asarray(tb["tokens"])
    reqs = []
    for rid, entry in enumerate(trace):
        batch = make_synthetic_batch(cfg, 1, entry.prompt_len,
                                     seed=seed + 1000 + rid,
                                     compute_dtype=dtype)
        prompt = {k: np.asarray(v) for k, v in batch.items() if k != "labels"}
        g = getattr(entry, "prefix_group", -1)
        if g >= 0 and entry.prefix_len > 0 and "tokens" in prompt:
            toks = prompt["tokens"].copy()
            toks[:, :entry.prefix_len] = templates[g][:, :entry.prefix_len]
            prompt["tokens"] = toks
        reqs.append(ServeRequest(rid=rid, batch=prompt,
                                 max_new_tokens=entry.max_new,
                                 temperature=entry.temperature,
                                 seed=seed, arrival=entry.arrival))
    return reqs


# ---------------------------------------------------------------------------
# Drivers
# ---------------------------------------------------------------------------

def _drive_wall_clock(target, requests: List[ServeRequest]) -> float:
    """Shared wall-clock traffic loop over anything with the serving
    drive surface (``submit``/``step``/``idle`` — an engine or a
    fabric): submit each request at its arrival time, run micro-steps
    until everything drains, return the makespan in seconds."""
    pending = sorted(requests, key=lambda r: r.arrival)
    n, i = len(pending), 0
    done = 0
    t0 = time.perf_counter()
    while done < n:
        now = time.perf_counter() - t0
        while i < n and pending[i].arrival <= now:
            target.submit(pending[i], now)
            i += 1
        if target.idle and i < n:
            time.sleep(min(1e-3, max(0.0, pending[i].arrival - now)))
            continue
        done += len(target.step(time.perf_counter() - t0))
    return time.perf_counter() - t0


def _attach_telemetry(stats: Dict) -> None:
    """When the tracer is live (``REPRO_TRACE=1``), stamp the trial's
    residual report, flat per-hop ratios, and the serialization-stall
    total onto the stats dict. The capture is trial-clean because every
    warm-up boundary (``engine.reset`` / ``fabric.close``) flushes the
    ledger before the measured drive starts."""
    tr = obs_trace.active()
    if tr is None:
        return
    rep = tr.residuals.report()
    stats["residual_report"] = rep
    for kind, row in rep["hops"].items():
        if row["n"]:
            stats[f"residual_{kind}_ratio"] = row["ratio"]
    stats["serialization_stall_s"] = rep["serialization_stall_s"]


def drive_continuous(eng: ContinuousEngine, requests: List[ServeRequest]
                     ) -> Dict[str, float]:
    """Wall-clock traffic loop through one continuous engine. Stats come
    from the one merged surface (:func:`repro.obs.metrics.snapshot`):
    latency percentiles, KV/prefix/spec accounting, and — when the
    registry is live — its counters/gauges/histograms."""
    makespan = _drive_wall_clock(eng, requests)
    toks = sum(useful_tokens(r.output[:r.generated], eng.eos_id)
               for r in requests)
    stats = obs_metrics.snapshot(engine=eng)
    stats.update(makespan_s=makespan, useful_tokens=float(toks),
                 tok_s=toks / makespan,
                 eager_admits=float(eng.scheduler.n_eager_admits),
                 deferred=float(eng.scheduler.n_deferred),
                 modeled_admit_cost_us=1e6
                 * eng.scheduler.modeled_admit_cost_s)
    _attach_telemetry(stats)
    return stats


def drive_static(eng: StaticEngine, requests: List[ServeRequest],
                 batch_size: int) -> Dict[str, float]:
    """Static-batch baseline: wait for ``batch_size`` arrivals, prefill
    them together, decode the whole batch to the slowest member. Requests
    are bucketed by prompt length (a static batch needs rectangular
    prompts), batches form FIFO within a bucket and run in order of their
    last member's arrival. The last partial batch is padded (repeat of
    its final row) so the jit shapes stay fixed; padding rows are not
    counted. Sampling is per-row — a mixed-temperature group samples each
    request at its own temperature; heterogeneous seeds in one group
    cannot be honored by the shared key chain and raise."""
    reqs = sorted(requests, key=lambda r: r.arrival)
    n = len(reqs)
    buckets: Dict[int, List[ServeRequest]] = {}
    for r in reqs:
        buckets.setdefault(r.prompt_len, []).append(r)
    groups = [rs[start:start + batch_size]
              for rs in buckets.values()
              for start in range(0, len(rs), batch_size)]
    groups.sort(key=lambda g: max(r.arrival for r in g))
    t0 = time.perf_counter()
    for group in groups:
        latest = max(r.arrival for r in group)
        while time.perf_counter() - t0 < latest:
            time.sleep(1e-3)
        seeds = {r.seed for r in group}
        if len(seeds) > 1:
            raise ValueError("drive_static: heterogeneous seeds in one "
                             f"static batch group: {sorted(seeds)}")
        rows = [r.batch for r in group]
        temps = [r.temperature for r in group]
        while len(rows) < batch_size:          # shape-stable padding
            rows.append(rows[-1])
            temps.append(temps[-1])
        batch = {k: np.concatenate([row[k] for row in rows])
                 for k in rows[0]}
        max_new = max(r.max_new_tokens for r in group)
        out = eng.generate(batch, max_new,
                           temperature=np.asarray(temps, np.float32),
                           seed=group[0].seed)
        now = time.perf_counter() - t0
        for j, r in enumerate(group):
            r.output = out[j, :r.max_new_tokens].copy()
            r.generated = useful_tokens(r.output, eng.eos_id)
            r.finish_time = now
    makespan = time.perf_counter() - t0
    toks = sum(r.generated for r in reqs)
    lat = np.array([r.finish_time - r.arrival for r in reqs])
    return {"n": float(n), "makespan_s": makespan,
            "useful_tokens": float(toks), "tok_s": toks / makespan,
            "latency_p50_s": float(np.percentile(lat, 50)),
            "latency_p95_s": float(np.percentile(lat, 95)),
            "latency_mean_s": float(lat.mean())}


def drive_fabric(fab: ServingFabric, requests: List[ServeRequest]
                 ) -> Dict[str, float]:
    """Wall-clock traffic loop through the serving fabric: the shared
    drive loop against the router's ``submit``/``step`` (dispatch →
    every rank → migrate) surface."""
    makespan = _drive_wall_clock(fab, requests)
    eos = fab.workers[0].engine.eos_id
    toks = sum(useful_tokens(r.output[:r.generated], eos) for r in requests)
    stats = obs_metrics.snapshot(extra=fab.stats())
    stats.update(makespan_s=makespan, useful_tokens=float(toks),
                 tok_s=toks / makespan)
    _attach_telemetry(stats)
    return stats


def warm_fabric(fab: ServingFabric, cfg, *, dtype: str, seed: int,
                prompt_len: int) -> None:
    """Compile every rank's jits off the clock (chunk + decode dispatch,
    and on the disaggregated path the migrate copy + state import), then
    reset the whole fabric — warm requests must leave no queue entries,
    leases, device state or accounting behind (PR-5 satellite: the
    scheduler's rid-keyed maps are exactly what this reset must clear).
    Four new tokens give every rank at least two decode dispatches, so
    its stream gates (which start at the second dispatch) compile here
    too."""
    trace = make_trace(2 * fab.ranks, prompt_len=prompt_len, max_new=4,
                       arrival="all", seed=seed + 7)
    for req in requests_from_trace(cfg, trace, dtype=dtype, seed=seed + 7):
        fab.submit(req, 0.0)
    guard = 0
    while not fab.idle:
        fab.step(0.0)
        guard += 1
        if guard > 10_000:
            raise RuntimeError("fabric warm-up failed to drain")
    fab.reset()


def run_fabric(arch: str = "gemma-2b", *, smoke: bool = True,
               requests: int = 16, ranks: int = 2, slots: int = 4,
               prompt_len=(16, 256), max_new=(4, 32),
               arrival: str = "poisson", rate: float = 50.0,
               burst: int = 4, temperature: float = 0.0, eos_id: int = -1,
               seed: int = 0, prefill_chunk: int = 64,
               max_prefill_per_step: int = 2, block_size: int = 16,
               placements=("replicated", "disagg"),
               n_prefill_ranks: int = 1, speculate: int = 0) -> Dict:
    """Fabric-vs-single comparison (DESIGN.md §10): drive the same
    arrival trace through a single paged ``ContinuousEngine`` and then
    through an N-rank :class:`ServingFabric` under each requested
    placement policy. Records aggregate tok/s and TTFT p50/p95 per
    policy, per-rank utilization, the disaggregated path's KV-migration
    accounting, and greedy token-identity of the replicated path against
    the single-engine baseline (every fabric rank runs the same chunked
    paged engine, so placement must not change a single sampled token).
    """
    cfg, dtype, model = serving_model(arch, smoke=smoke)
    if model.decode_step_paged is None:
        raise ValueError(f"arch {cfg.name!r} has no paged decode path; "
                         "the serving fabric runs paged engines only")
    prefill_chunk = effective_chunk(model.capabilities, prefill_chunk)
    params = model.init(jax.random.PRNGKey(seed))
    plens = ((int(prompt_len),) if isinstance(prompt_len, int)
             else tuple(int(p) for p in prompt_len))
    pmax = max(plens)
    hi = max_new if isinstance(max_new, int) else max_new[1]
    cache_len = pmax + hi

    trace = make_trace(requests, prompt_len=plens, max_new=max_new,
                       arrival=arrival, rate=rate, burst=burst,
                       temperature=temperature, seed=seed)
    result: Dict = {"arch": cfg.name, "requests": requests, "ranks": ranks,
                    "slots_per_rank": slots, "prompt_len": list(plens),
                    "cache_len": cache_len, "arrival": arrival,
                    "rate": rate, "eos_id": eos_id,
                    "prefill_chunk": prefill_chunk,
                    "block_size": block_size,
                    "n_prefill_ranks": n_prefill_ranks,
                    "placements": list(placements)}

    # -- single-engine baseline (one paged engine, same per-rank size) --
    eng = ContinuousEngine(model, params, cache_len=cache_len,
                           num_slots=slots, eos_id=eos_id,
                           prefill_chunk=prefill_chunk,
                           max_prefill_per_step=max_prefill_per_step,
                           kv_layout="paged", block_size=block_size)
    warm = {k: np.asarray(v) for k, v in make_synthetic_batch(
        cfg, 1, plens[0], seed=seed, compute_dtype=dtype).items()
        if k != "labels"}
    eng.generate({k: np.concatenate([v] * min(2, eng.kv.num_slots))
                  for k, v in warm.items()}, 2)
    eng.reset()
    base_reqs = requests_from_trace(cfg, trace, dtype=dtype, seed=seed)
    result["single"] = drive_continuous(eng, base_reqs)

    # -- fabric runs, one per placement policy --
    for placement in placements:
        # speculative fabric ranks are replicated-only (a disaggregated
        # decode rank imports leases the verify pool cannot host) and
        # greedy-only; PR 9's token-identity guarantee keeps the spec
        # replicated fabric comparable to the non-spec single baseline
        spec_k = (speculate if (placement == "replicated"
                                and temperature == 0.0
                                and model.verify_step_paged is not None)
                  else 0)
        result[f"fabric_speculate_k_{placement}"] = spec_k
        fab = ServingFabric(model, params, ranks=ranks,
                            placement=placement, cache_len=cache_len,
                            slots_per_rank=slots, eos_id=eos_id,
                            prefill_chunk=prefill_chunk,
                            max_prefill_per_step=max_prefill_per_step,
                            block_size=block_size,
                            n_prefill_ranks=n_prefill_ranks,
                            speculate=spec_k)
        try:
            warm_fabric(fab, cfg, dtype=dtype, seed=seed,
                        prompt_len=plens[0])
            reqs = requests_from_trace(cfg, trace, dtype=dtype, seed=seed)
            result[f"fabric_{placement}"] = drive_fabric(fab, reqs)
            ident = bool(all(
                np.array_equal(a.output[:a.generated],
                               b.output[:b.generated])
                for a, b in zip(base_reqs, reqs)))
            result[f"fabric_token_identical_{placement}"] = ident
            spd = (result[f"fabric_{placement}"]["tok_s"]
                   / result["single"]["tok_s"])
            result[f"fabric_{placement}"]["speedup_vs_single"] = spd
            # first-class comparison key: N ranks must beat one rank of
            # the same size — the number CI gates on (a fabric that
            # loses to its own single-engine baseline is a routing or
            # placement regression, not a measurement detail)
            result[f"speedup_vs_single_{placement}"] = spd
        finally:
            fab.close()
    return result


def run_family_rows(archs=FAMILY_ARCHS, *, smoke: bool = True,
                    requests: int = 6, slots: int = 4,
                    prompt_len: int = 24, max_new: int = 4,
                    prefill_chunk: int = 16, block_size: int = 8,
                    eos_id: int = -1, seed: int = 0) -> List[Dict]:
    """Per-family serving rows (``--config``, schema v7): drive a small
    same-arrival trace through each family's continuous *paged* chunked
    engine and report ``continuous_tok_s`` plus token identity against
    the family's static monolithic baseline. One row per registry
    family; a family whose structure forbids the path (patch_stub)
    reports its capability reason instead of faking a number."""
    rows: List[Dict] = []
    for arch in archs:
        cfg, dtype, model = serving_model(arch, smoke=smoke)
        caps = model.capabilities
        row: Dict = {"family": cfg.name, "block": cfg.block,
                     "chunked_prefill": bool(caps.chunked_prefill),
                     "paged_decode": bool(caps.paged_decode),
                     "carried_state": bool(caps.carried_state),
                     "prefix_cache": bool(caps.prefix_cache),
                     "kv_migration": bool(caps.kv_migration),
                     "speculative": bool(caps.speculative)}
        chunk = effective_chunk(caps, prefill_chunk)
        if not (chunk and caps.paged_decode):
            row["skipped"] = caps.reason
            rows.append(row)
            continue
        row["prefill_chunk"] = chunk
        params = model.init(jax.random.PRNGKey(seed))
        cache_len = prompt_len + max_new
        trace = make_trace(requests, prompt_len=prompt_len,
                           max_new=max_new, arrival="all", seed=seed)
        reqs = requests_from_trace(cfg, trace, dtype=dtype, seed=seed)
        eng = ContinuousEngine(model, params, cache_len=cache_len,
                               num_slots=slots, eos_id=eos_id,
                               prefill_chunk=chunk, kv_layout="paged",
                               block_size=block_size)
        stats = drive_continuous(eng, reqs)
        row["continuous_tok_s"] = stats["tok_s"]
        row["ttft_p95_s"] = stats.get("ttft_p95_s")
        row["state_bytes_per_slot"] = eng._carried_state_bytes()
        # static monolithic baseline on the same prompts: the greedy
        # tokens must be identical (the family-parity contract)
        batch = {k: np.concatenate([r.batch[k] for r in reqs])
                 for k in reqs[0].batch}
        s_out = StaticEngine(model, params, cache_len=cache_len,
                             eos_id=eos_id).generate(batch, max_new)
        row["static_tok_identical"] = bool(all(
            np.array_equal(s_out[j, :r.generated],
                           r.output[:r.generated])
            for j, r in enumerate(reqs)))
        rows.append(row)
    return rows


# ---------------------------------------------------------------------------
# End-to-end harness (imported by benchmarks/bench_serve.py)
# ---------------------------------------------------------------------------

def run_traffic(arch: str = "gemma-2b", *, smoke: bool = True,
                requests: int = 12, slots: int = 4, prompt_len=16,
                max_new=(4, 32), arrival: str = "poisson",
                rate: float = 50.0, burst: int = 4, temperature: float = 0.0,
                engine: str = "both", ring: bool = False, eos_id: int = -1,
                seed: int = 0, parity_check: bool = True,
                prefill_chunk: int = 64, max_prefill_per_step: int = 2,
                chunk_compare: bool = True, paged_compare: bool = True,
                block_size: int = 16, prefix_compare: bool = True,
                shared_prefix_len: int = 0,
                share_ratio: float = 0.9, spec_compare: bool = True,
                speculate: int = 3, draft_arch: str = "self") -> Dict:
    """Build the model once, warm the jits, then drive the trace through
    the requested engine(s). Returns the full measurement dict.

    ``prompt_len`` is an int or a sequence cycled across the trace (e.g.
    ``(16, 256)`` interleaves short and long prompts — the trace that
    exposes prefill head-of-line blocking). With ``chunk_compare`` the
    continuous engine runs twice, chunked (``prefill_chunk``) and
    monolithic, and the result records the TTFT comparison plus prefill
    compile counts. Warm-up compiles one prompt shape off the clock; the
    monolithic engine must still compile every *other* distinct prompt
    length mid-traffic, which is exactly the cost the chunked path
    removes (its chunk jit never sees a new shape).

    With ``paged_compare`` (and an arch exposing the paged decode path)
    the continuous engine runs once more over a *paged* KV pool sized to
    the slot pool's HBM budget (``slots * cache_len`` tokens repartitioned
    into ``block_size``-token blocks, request rows no longer the scarce
    resource): the result records token-identity against the slot run,
    resident KV bytes/token, and peak concurrent requests at equal HBM —
    the paged engine must sustain strictly more.

    With ``prefix_compare`` (and a paged+chunkable arch) the driver also
    runs a shared-prefix trace (``shared_prefix_len`` template tokens,
    default ~3/4 of the longest prompt; ``share_ratio`` of requests in
    one of two template families) through three configurations: a paged
    engine without the radix prefix cache, a prefix-cached engine cold,
    and the same engine warm (``reset(preserve_prefix=True)`` — the
    repeat-tenant shape). All three must be token-identical; the warm
    pass's hit rate, prefill work saved, and TTFT improvement land as
    top-level keys (DESIGN.md §12).

    With ``spec_compare`` (greedy traces on a speculative-capable arch)
    the same trace runs once more through a paged engine with
    ``speculate=k`` draft–verify rounds (DESIGN.md §14):
    ``draft_arch="self"`` self-speculates (the target drafts on a second
    pool — full machinery, near-1.0 acceptance), any other name builds
    that config as the drafter. The result records ``spec_tok_s``
    alongside the non-speculative paged run's throughput, per-dispatch
    acceptance, and trace-level token identity — speculation must not
    change one greedy token (schema v7).
    """
    cfg, dtype, model = serving_model(arch, smoke=smoke, ring=ring)
    # capability-aware chunk selection (DESIGN.md §13): floor the chunk
    # to the family's multiple; patch_stub frontends run monolithic; an
    # enc-dec family chunks on the paged path only, so its slot runs
    # deposit monolithically while the paged comparison still chunks
    caps = model.capabilities
    prefill_chunk = effective_chunk(caps, prefill_chunk)
    slot_chunk = prefill_chunk if caps.slot_chunk else 0
    params = model.init(jax.random.PRNGKey(seed))
    plens = ((int(prompt_len),) if isinstance(prompt_len, int)
             else tuple(int(p) for p in prompt_len))
    pmax = max(plens)
    hi = max_new if isinstance(max_new, int) else max_new[1]
    cache_len = (min(cfg.swa_window, pmax + hi)
                 if ring and cfg.swa_window else pmax + hi)

    trace = make_trace(requests, prompt_len=plens, max_new=max_new,
                       arrival=arrival, rate=rate, burst=burst,
                       temperature=temperature, seed=seed)
    result: Dict = {"arch": cfg.name, "requests": requests, "slots": slots,
                    "prompt_len": list(plens), "cache_len": cache_len,
                    "arrival": arrival, "rate": rate, "eos_id": eos_id,
                    "prefill_chunk": 0,     # effective value set below
                    "max_prefill_per_step": max_prefill_per_step,
                    "distinct_prompt_lens": len(set(plens))}

    warm = {k: np.asarray(v) for k, v in make_synthetic_batch(
        cfg, 1, plens[0], seed=seed, compute_dtype=dtype).items()
        if k != "labels"}

    def _drive_continuous(chunk: int, kv_layout: str = "slot",
                          num_blocks=None, n_rows=None, speculate=0,
                          draft_model=None, draft_params=None):
        # the engine's default scheduler prices admissions with the
        # engine's own (cache_len-clamped) chunk size
        eng = ContinuousEngine(
            model, params, cache_len=cache_len, num_slots=n_rows or slots,
            eos_id=eos_id, prefill_chunk=chunk,
            max_prefill_per_step=max_prefill_per_step,
            kv_layout=kv_layout, block_size=block_size,
            num_blocks=num_blocks, speculate=speculate,
            draft_model=draft_model, draft_params=draft_params)
        # warm the jits on ONE prompt shape off the clock, then reset the
        # engine — warm requests must leave neither stale device slot
        # state nor accounting rows behind
        eng.generate({k: np.concatenate([v] * min(2, eng.kv.num_slots))
                      for k, v in warm.items()}, 2)
        eng.reset()
        warm_compiles = eng.prefill_compiles
        reqs = requests_from_trace(cfg, trace, dtype=dtype, seed=seed)
        stats = drive_continuous(eng, reqs)
        stats["prefill_chunk"] = float(eng.prefill_chunk)
        stats["prefill_compiles_total"] = float(eng.prefill_compiles)
        stats["prefill_compiles_drive"] = float(
            eng.prefill_compiles - warm_compiles)
        stats.update(eng.kv_accounting())
        stats["block_deferrals"] = float(eng.scheduler.n_block_deferrals)
        if speculate:
            stats.update(eng.spec_stats())
            stats["decode_tokens_per_dispatch"] = \
                eng.decode_tokens_per_dispatch
        return stats, reqs

    if engine in ("continuous", "both"):
        result["continuous"], slot_reqs = _drive_continuous(slot_chunk)
        # effective chunk size, read back from the engine (clamped to the
        # slot capacity and floored to the family's chunk multiple; 0 =
        # explicit monolithic, e.g. enc-dec on the slot layout) — the
        # artifact records real behavior, and a monolithic run must not
        # fake a chunked-vs-monolithic comparison of two identical runs
        eff_chunk = int(result["continuous"]["prefill_chunk"])
        result["prefill_chunk"] = eff_chunk
        if eff_chunk and chunk_compare:
            result["continuous_monolithic"], _ = _drive_continuous(0)
            c, m = result["continuous"], result["continuous_monolithic"]
            if "ttft_p95_s" in c and "ttft_p95_s" in m:
                result["ttft_p95_chunked_s"] = c["ttft_p95_s"]
                result["ttft_p95_monolithic_s"] = m["ttft_p95_s"]
                result["chunked_ttft_p95_improved"] = bool(
                    c["ttft_p95_s"] < m["ttft_p95_s"])
            result["prefill_compiles_prompt_len_independent"] = bool(
                c["prefill_compiles_total"] <= 1.0)
        if (prefill_chunk and paged_compare
                and model.decode_step_paged is not None):
            # equal-HBM paged run: repartition the slot pool's token
            # capacity into leased blocks; request rows (cheap host state)
            # stop being the scarce resource, blocks gate admission
            nblocks = max(1, (slots * cache_len) // block_size)
            rows = min(requests, nblocks)
            result["continuous_paged"], paged_reqs = _drive_continuous(
                prefill_chunk, kv_layout="paged", num_blocks=nblocks,
                n_rows=rows)
            c, p = result["continuous"], result["continuous_paged"]
            result["block_size"] = block_size
            result["paged_num_blocks"] = nblocks
            result["paged_token_identical_trace"] = bool(all(
                np.array_equal(a.output[:a.generated], b.output[:b.generated])
                for a, b in zip(slot_reqs, paged_reqs)))
            result["paged_hbm_within_budget"] = bool(
                p["kv_bytes_total"] <= c["kv_bytes_total"])
            result["paged_max_concurrency"] = p["peak_concurrent"]
            result["slot_max_concurrency"] = c["peak_concurrent"]
            result["paged_more_concurrent_verified"] = bool(
                p["peak_concurrent"] > c["peak_concurrent"])
            result["paged_bytes_per_resident_token"] = \
                p["kv_bytes_per_resident_token"]
            result["slot_bytes_per_resident_token"] = \
                c["kv_bytes_per_resident_token"]

        if (prefill_chunk and spec_compare and speculate > 0
                and temperature == 0.0
                and model.verify_step_paged is not None):
            # speculative run over the SAME trace and paged pool
            # geometry as the non-spec paged comparison: k-token
            # draft–verify rounds, greedy parity required token-for-
            # token (DESIGN.md §14). Skipped (never faked) on sampled
            # traces and on families without the 'speculative'
            # capability.
            draft_model = draft_params = None
            if draft_arch not in ("self", arch):
                _, _, draft_model = serving_model(draft_arch, smoke=smoke,
                                                  ring=ring)
                draft_params = draft_model.init(jax.random.PRNGKey(seed))
            nblocks = max(1, (slots * cache_len) // block_size)
            rows = min(requests, nblocks)
            result["continuous_spec"], spec_reqs = _drive_continuous(
                prefill_chunk, kv_layout="paged", num_blocks=nblocks,
                n_rows=rows, speculate=speculate,
                draft_model=draft_model, draft_params=draft_params)
            sp = result["continuous_spec"]
            ref = result.get("continuous_paged")
            ref_reqs = paged_reqs if ref is not None else slot_reqs
            if ref is None:
                ref = result["continuous"]
            result["speculate_k"] = speculate
            result["draft_arch"] = draft_arch
            result["spec_tok_s"] = sp["tok_s"]
            result["continuous_tok_s"] = ref["tok_s"]
            result["spec_accepted_per_dispatch"] = \
                sp["accepted_per_dispatch"]
            result["spec_acceptance_rate"] = sp["acceptance_rate"]
            result["spec_token_identical_trace"] = bool(all(
                np.array_equal(a.output[:a.generated],
                               b.output[:b.generated])
                for a, b in zip(ref_reqs, spec_reqs)))

        if (prefill_chunk and prefix_compare
                and model.decode_step_paged is not None
                and model.clone_paged_block is not None):
            bs = block_size
            spl = (int(shared_prefix_len) if shared_prefix_len > 0
                   else (3 * pmax // 4) // bs * bs)
            spl = max(bs, min(spl, pmax - 1))
            groups = 2
            trace_pfx = make_trace(
                requests, prompt_len=pmax, max_new=max_new,
                arrival=arrival, rate=rate, burst=burst,
                temperature=temperature, shared_prefix_len=spl,
                share_ratio=share_ratio, prefix_groups=groups, seed=seed)
            # pool sized for live requests PLUS the parked prefix index:
            # the shared templates, every request's private tail chain,
            # and headroom — the bench measures hit behavior, not
            # eviction churn (tests/test_prefix_cache.py covers that)
            nblocks_pfx = (slots * -(-cache_len // bs)
                           + groups * -(-spl // bs)
                           + requests * (-(-(pmax - spl) // bs) + 2))

            def _mk_pfx(prefix_cache: bool) -> ContinuousEngine:
                e = ContinuousEngine(
                    model, params, cache_len=cache_len, num_slots=slots,
                    eos_id=eos_id, prefill_chunk=prefill_chunk,
                    max_prefill_per_step=max_prefill_per_step,
                    kv_layout="paged", block_size=bs,
                    num_blocks=nblocks_pfx, prefix_cache=prefix_cache)
                e.generate({k: np.concatenate([v] * min(2, e.kv.num_slots))
                            for k, v in warm.items()}, 2)
                if prefix_cache:
                    # compile the CoW clone off the clock too (a self-
                    # clone is a no-op on the pool contents) — the first
                    # partial-block hit otherwise pays it mid-traffic
                    e.kv.swap_buffers(e._cow_clone(
                        e.kv.buffers, jnp.int32(0), jnp.int32(0)))
                e.reset()          # full reset: warm-up prompts must not
                return e           # pre-seed the trie

            base_reqs = requests_from_trace(cfg, trace_pfx, dtype=dtype,
                                            seed=seed)
            base_stats = drive_continuous(_mk_pfx(False), base_reqs)

            peng = _mk_pfx(True)
            cold_reqs = requests_from_trace(cfg, trace_pfx, dtype=dtype,
                                            seed=seed)
            cold_stats = drive_continuous(peng, cold_reqs)
            cold_stats.update(peng.prefix_stats())
            # warm: rows drain, the trie (and device KV) survives — the
            # repeat-tenant pass every hit block is already resident for
            peng.reset(preserve_prefix=True)
            warm_reqs = requests_from_trace(cfg, trace_pfx, dtype=dtype,
                                            seed=seed)
            warm_stats = drive_continuous(peng, warm_reqs)
            warm_stats.update(peng.prefix_stats())

            ident = bool(all(
                np.array_equal(a.output[:a.generated],
                               b.output[:b.generated])
                and np.array_equal(a.output[:a.generated],
                                   w.output[:w.generated])
                for a, b, w in zip(base_reqs, cold_reqs, warm_reqs)))
            result["prefix"] = {
                "shared_prefix_len": spl, "share_ratio": share_ratio,
                "prefix_groups": groups, "num_blocks": nblocks_pfx,
                "prompt_len": pmax, "baseline": base_stats,
                "cold": cold_stats, "warm": warm_stats,
            }
            result["prefix_token_identical"] = ident
            result["prefix_hit_rate"] = warm_stats["prefix_hit_rate"]
            result["prefill_tokens_saved"] = \
                warm_stats["prefill_tokens_saved"]
            result["prefill_dispatches_saved"] = \
                warm_stats["prefill_dispatches_saved"]
            if ("ttft_p95_s" in warm_stats and "ttft_p95_s" in cold_stats):
                result["prefix_ttft_p95_improved"] = bool(
                    warm_stats["ttft_p95_s"] < cold_stats["ttft_p95_s"])

    if engine in ("static", "both"):
        seng = StaticEngine(model, params, cache_len=cache_len, eos_id=eos_id)
        seng.generate({k: np.concatenate([v] * slots)
                       for k, v in warm.items()}, 2)    # warm jits
        result["static"] = drive_static(
            seng, requests_from_trace(cfg, trace, dtype=dtype, seed=seed),
            batch_size=slots)

    if engine == "both":
        spd = result["continuous"]["tok_s"] / result["static"]["tok_s"]
        result["speedup_tok_s"] = spd
        result["continuous_faster_verified"] = bool(spd > 1.0)

    if parity_check:
        # parity at the LONGEST prompt length: a multi-chunk deposit must
        # be token-identical to the monolithic static prefill. The decode
        # budget is capped by the trace's max_new ceiling — cache_len (and
        # therefore the paged engine's admittable capacity) is sized to
        # pmax + hi, so a fixed 8 would overflow it when hi < 8
        B = min(4, slots)
        par_new = min(8, hi)
        pbatch = make_synthetic_batch(cfg, B, pmax, seed=seed + 1,
                                      compute_dtype=dtype)
        prompt = {k: np.asarray(v) for k, v in pbatch.items()
                  if k != "labels"}
        s_out = StaticEngine(model, params, cache_len=cache_len,
                             eos_id=eos_id).generate(prompt, par_new)
        c_out = ContinuousEngine(model, params, cache_len=cache_len,
                                 num_slots=B, eos_id=eos_id,
                                 prefill_chunk=slot_chunk,
                                 max_prefill_per_step=max_prefill_per_step,
                                 ).generate(prompt, par_new)
        result["parity_token_identical"] = bool(np.array_equal(s_out, c_out))
        result["parity_prompt_len"] = pmax
        if (paged_compare and model.decode_step_paged is not None
                and prefill_chunk):
            p_out = ContinuousEngine(
                model, params, cache_len=cache_len, num_slots=B,
                eos_id=eos_id, prefill_chunk=prefill_chunk,
                max_prefill_per_step=max_prefill_per_step,
                kv_layout="paged",
                block_size=block_size).generate(prompt, par_new)
            result["parity_token_identical_paged"] = bool(
                np.array_equal(s_out, p_out))
    return result


def _collect_reports(obj) -> List[dict]:
    """Every sub-run residual report nested anywhere in a payload (the
    drivers stamp one per measured trial)."""
    reps: List[dict] = []
    if isinstance(obj, dict):
        rep = obj.get("residual_report")
        if isinstance(rep, dict):
            reps.append(rep)
        for v in obj.values():
            if isinstance(v, (dict, list)):
                reps.extend(_collect_reports(v))
    elif isinstance(obj, list):
        for v in obj:
            reps.extend(_collect_reports(v))
    return reps


def _finalize_payload(payload: Dict) -> Dict:
    """Schema v8: merge every sub-run's residual report into one
    payload-level ``residual_report`` with flat ``residual_<hop>_ratio``
    keys and the summed ``serialization_stall_s`` (all absent when
    telemetry was off). Every payload names the device it ran on."""
    dev = jax.devices()[0]
    payload["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices())}
    reps = _collect_reports(payload)
    if reps:
        merged = obs_residuals.merge_reports(reps)
        payload["residual_report"] = merged
        for kind, row in merged["hops"].items():
            if row["n"]:
                payload[f"residual_{kind}_ratio"] = row["ratio"]
        payload["serialization_stall_s"] = merged["serialization_stall_s"]
    return payload


def _write_trace(path) -> None:
    """``--trace-out``: export the tracer's ring as Chrome trace_event
    JSON (Perfetto / chrome://tracing)."""
    if not path:
        return
    tr = obs_trace.active()
    if tr is None:
        print(f"--trace-out {path}: tracing is off (set REPRO_TRACE=1)")
        return
    tr.write_chrome(path)
    print(f"wrote {path} ({tr.n_events} events, {tr.dropped} dropped)")


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b", choices=list(ARCH_NAMES))
    ap.add_argument("--config", default=None, metavar="NAME[,NAME...]",
                    help="per-family serving rows: drive each named "
                         "registry config (or 'families' = one per "
                         "serving structure) through the continuous "
                         "paged engine and emit continuous_tok_s rows "
                         "(schema v6) instead of the engine comparison")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", default="both",
                    choices=["static", "continuous", "both"])
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", default="16", metavar="N[,N...]",
                    help="prompt length, or a comma list cycled across "
                         "the trace (e.g. 16,256)")
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="prompt chunk size for the continuous engine "
                         "(0 = monolithic prefill)")
    ap.add_argument("--max-prefill-per-step", type=int, default=2,
                    help="chunk-rows batched into one prefill dispatch")
    ap.add_argument("--no-chunk-compare", action="store_true",
                    help="skip the monolithic-prefill comparison run")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="tokens per KV block for the paged comparison run")
    ap.add_argument("--no-paged-compare", action="store_true",
                    help="skip the paged-KV comparison run")
    ap.add_argument("--no-prefix-compare", action="store_true",
                    help="skip the radix prefix-cache comparison run")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="template tokens shared by the prefix-compare "
                         "trace (0 = ~3/4 of the longest prompt)")
    ap.add_argument("--share-ratio", type=float, default=0.9,
                    help="fraction of prefix-compare requests drawn from "
                         "a shared template family")
    ap.add_argument("--speculate", type=int, default=3,
                    help="draft tokens per draft-verify round for the "
                         "speculative comparison run (0 = off)")
    ap.add_argument("--draft-arch", default="self",
                    help="drafter config for the speculative run; 'self' "
                         "= self-speculation (the target drafts on its "
                         "own second pool)")
    ap.add_argument("--no-spec-compare", action="store_true",
                    help="skip the speculative-decoding comparison run")
    ap.add_argument("--fabric", default="off",
                    choices=["off", "replicated", "disagg", "both"],
                    help="run the multi-rank serving fabric comparison "
                         "instead of the engine comparison (DESIGN.md §10)")
    ap.add_argument("--ranks", type=int, default=2,
                    help="engine ranks in the serving fabric")
    ap.add_argument("--prefill-ranks", type=int, default=1,
                    help="dedicated prefill ranks (disaggregated fabric)")
    ap.add_argument("--fabric-speculate", type=int, default=0,
                    help="draft tokens per draft-verify round on the "
                         "fabric's replicated ranks (0 = off; greedy "
                         "traces only, replicated placement only)")
    ap.add_argument("--max-new-lo", type=int, default=4)
    ap.add_argument("--max-new-hi", type=int, default=32)
    ap.add_argument("--arrival", default="poisson",
                    choices=["poisson", "burst", "all"])
    ap.add_argument("--rate", type=float, default=50.0,
                    help="arrival rate (req/s); burst spacing is 1/rate")
    ap.add_argument("--burst", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--eos-id", type=int, default=-1)
    ap.add_argument("--ring", action="store_true",
                    help="ring-buffer KV slots (sub-quadratic archs)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write measurements (e.g. BENCH_serve.json)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the telemetry ring as Chrome trace_event "
                         "JSON for Perfetto (needs REPRO_TRACE=1)")
    args = ap.parse_args()

    plens = [int(x) for x in str(args.prompt_len).split(",") if x]
    if args.config is not None:
        archs = (FAMILY_ARCHS if args.config in ("families", "all")
                 else tuple(x for x in args.config.split(",") if x))
        for a in archs:
            if a not in ARCH_NAMES:
                ap.error(f"--config: unknown arch {a!r} "
                         f"(known: {sorted(ARCH_NAMES)})")
        rows = run_family_rows(
            archs, smoke=args.smoke, requests=args.requests,
            slots=args.slots, prompt_len=plens[0],
            max_new=args.max_new_hi, prefill_chunk=args.prefill_chunk,
            block_size=args.kv_block_size, eos_id=args.eos_id,
            seed=args.seed)
        for row in rows:
            if "skipped" in row:
                print(f"{row['family']:>14}: skipped ({row['skipped']})")
                continue
            print(f"{row['family']:>14}: "
                  f"{row['continuous_tok_s']:8.1f} tok/s  "
                  f"chunk {row['prefill_chunk']}  "
                  f"state_bytes/slot {row['state_bytes_per_slot']}  "
                  f"token_identical={row['static_tok_identical']}")
        if args.json:
            payload = _finalize_payload(
                {"schema": "repro-serve-bench-v8", "families": rows})
            with open(args.json, "w") as f:
                json.dump(payload, f, indent=1)
            print(f"wrote {args.json}")
        _write_trace(args.trace_out)
        return

    if args.fabric != "off":
        placements = (("replicated", "disagg") if args.fabric == "both"
                      else (args.fabric,))
        result = run_fabric(
            args.arch, smoke=args.smoke, requests=args.requests,
            ranks=args.ranks, slots=args.slots,
            prompt_len=plens[0] if len(plens) == 1 else plens,
            max_new=(args.max_new_lo, args.max_new_hi),
            arrival=args.arrival, rate=args.rate, burst=args.burst,
            temperature=args.temperature, eos_id=args.eos_id,
            seed=args.seed, prefill_chunk=args.prefill_chunk,
            max_prefill_per_step=args.max_prefill_per_step,
            block_size=args.kv_block_size, placements=placements,
            n_prefill_ranks=args.prefill_ranks,
            speculate=args.fabric_speculate)
        print(f"arch={result['arch']} requests={result['requests']} "
              f"ranks={result['ranks']} slots/rank="
              f"{result['slots_per_rank']} prompt_len="
              f"{result['prompt_len']}")
        for name in ("single", "fabric_replicated", "fabric_disagg"):
            if name not in result:
                continue
            m = result[name]
            ttft = (f"  ttft_p95 {m['ttft_p95_s'] * 1e3:.0f}ms"
                    if "ttft_p95_s" in m else "")
            print(f"{name:>18}: {m['tok_s']:8.1f} tok/s  "
                  f"makespan {m['makespan_s']:.2f}s  "
                  f"p50 {m['latency_p50_s'] * 1e3:.0f}ms  "
                  f"p95 {m['latency_p95_s'] * 1e3:.0f}ms{ttft}")
            for row in m.get("per_rank", ()):
                print(f"{'':>18}  rank {row['rank']} [{row['role']:>9}] "
                      f"util {row['utilization']:.2f}  "
                      f"dispatched {row['dispatched']:.0f}  "
                      f"migrated {row['migrated_in']:.0f}in/"
                      f"{row['migrated_out']:.0f}out  "
                      f"tokens {row['tokens']:.0f}")
            if "n_migrations" in m:
                print(f"{'':>18}  kv_migration: {m['n_migrations']:.0f} "
                      f"handoffs, {m['blocks_moved']:.0f} blocks, "
                      f"p95 {m.get('kv_migration_p95_us', 0.0):.1f}us "
                      f"modeled")
        for p in result["placements"]:
            print(f"   token_identical[{p}]="
                  f"{result.get(f'fabric_token_identical_{p}')}  "
                  f"speedup_vs_single[{p}]="
                  f"{result.get(f'speedup_vs_single_{p}', 0.0):.2f}x")
        if args.json:
            payload = _finalize_payload(
                {"schema": "repro-serve-bench-v8", **result})
            with open(args.json, "w") as f:
                json.dump(payload, f, indent=1)
            print(f"wrote {args.json}")
        _write_trace(args.trace_out)
        return

    result = run_traffic(
        args.arch, smoke=args.smoke, requests=args.requests,
        slots=args.slots, prompt_len=plens[0] if len(plens) == 1 else plens,
        max_new=(args.max_new_lo, args.max_new_hi), arrival=args.arrival,
        rate=args.rate, burst=args.burst, temperature=args.temperature,
        engine=args.engine, ring=args.ring, eos_id=args.eos_id,
        seed=args.seed, prefill_chunk=args.prefill_chunk,
        max_prefill_per_step=args.max_prefill_per_step,
        chunk_compare=not args.no_chunk_compare,
        paged_compare=not args.no_paged_compare,
        block_size=args.kv_block_size,
        prefix_compare=not args.no_prefix_compare,
        shared_prefix_len=args.shared_prefix_len,
        share_ratio=args.share_ratio,
        spec_compare=not args.no_spec_compare,
        speculate=args.speculate, draft_arch=args.draft_arch)

    print(f"arch={result['arch']} requests={result['requests']} "
          f"slots={result['slots']} cache_len={result['cache_len']} "
          f"prompt_len={result['prompt_len']} "
          f"prefill_chunk={result['prefill_chunk']}")
    for name in ("static", "continuous_monolithic", "continuous",
                 "continuous_paged", "continuous_spec"):
        if name in result:
            m = result[name]
            ttft = (f"  ttft_p95 {m['ttft_p95_s'] * 1e3:.0f}ms"
                    if "ttft_p95_s" in m else "")
            compiles = (f"  prefill_compiles {m['prefill_compiles_total']:.0f}"
                        if "prefill_compiles_total" in m else "")
            print(f"{name:>21}: {m['tok_s']:8.1f} tok/s  "
                  f"makespan {m['makespan_s']:.2f}s  "
                  f"p50 {m['latency_p50_s'] * 1e3:.0f}ms  "
                  f"p95 {m['latency_p95_s'] * 1e3:.0f}ms"
                  f"{ttft}{compiles}")
    if "speedup_tok_s" in result:
        print(f"    speedup: {result['speedup_tok_s']:.2f}x "
              f"(verified={result['continuous_faster_verified']})")
    if "chunked_ttft_p95_improved" in result:
        print(f"    chunked ttft_p95 {result['ttft_p95_chunked_s']*1e3:.0f}ms"
              f" vs monolithic {result['ttft_p95_monolithic_s']*1e3:.0f}ms "
              f"(improved={result['chunked_ttft_p95_improved']}, "
              f"compile-count prompt-len independent="
              f"{result.get('prefill_compiles_prompt_len_independent')})")
    if "paged_max_concurrency" in result:
        print(f"      paged: {result['paged_max_concurrency']:.0f} vs "
              f"{result['slot_max_concurrency']:.0f} peak concurrent at "
              f"equal HBM (block={result['block_size']} tok x "
              f"{result['paged_num_blocks']} blocks; more_concurrent="
              f"{result['paged_more_concurrent_verified']}, "
              f"bytes/resident-tok {result['paged_bytes_per_resident_token']:.0f}"
              f" vs {result['slot_bytes_per_resident_token']:.0f}, "
              f"token_identical={result['paged_token_identical_trace']})")
    if "spec_tok_s" in result:
        print(f"       spec: k={result['speculate_k']} "
              f"(draft={result['draft_arch']})  "
              f"{result['spec_tok_s']:.1f} tok/s vs "
              f"{result['continuous_tok_s']:.1f} non-spec  "
              f"accepted/dispatch "
              f"{result['spec_accepted_per_dispatch']:.2f}  "
              f"acceptance {result['spec_acceptance_rate']:.3f}  "
              f"token_identical={result['spec_token_identical_trace']}")
    if "prefix" in result:
        pfx = result["prefix"]
        warm_ttft = pfx["warm"].get("ttft_p95_s", 0.0)
        cold_ttft = pfx["cold"].get("ttft_p95_s", 0.0)
        print(f"     prefix: hit_rate {result['prefix_hit_rate']:.3f}  "
              f"tokens_saved {result['prefill_tokens_saved']:.0f}  "
              f"dispatches_saved {result['prefill_dispatches_saved']:.0f}  "
              f"cow {pfx['warm'].get('prefix_cow_clones', 0.0):.0f}  "
              f"ttft_p95 warm {warm_ttft * 1e3:.0f}ms vs cold "
              f"{cold_ttft * 1e3:.0f}ms "
              f"(improved={result.get('prefix_ttft_p95_improved')}, "
              f"token_identical={result['prefix_token_identical']}, "
              f"shared_len={pfx['shared_prefix_len']})")
    if "parity_token_identical" in result:
        print(f"     parity: token_identical="
              f"{result['parity_token_identical']} "
              f"paged={result.get('parity_token_identical_paged')} "
              f"(prompt_len={result.get('parity_prompt_len')})")
    if args.json:
        payload = _finalize_payload(
            {"schema": "repro-serve-bench-v8", **result})
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"wrote {args.json}")
    _write_trace(args.trace_out)


if __name__ == "__main__":
    main()
