"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus section comments). Pass
``--fast`` to skip the multi-device subprocess measurements (models and
artifact-derived rows only); pass ``--json PATH`` to also emit the rows as
a machine-readable artifact (e.g. ``BENCH_collectives.json``) so the perf
trajectory accumulates across commits (the CI workflow uploads it)."""

from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import traceback

# runnable both as `python -m benchmarks.run` and `python benchmarks/run.py`
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip subprocess wall-time measurements")
    ap.add_argument("--only", default=None,
                    help="run a single bench module (p2p|barrier|reduce|"
                         "spmv|collectives|serve|fabric)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as JSON (e.g. "
                         "BENCH_collectives.json)")
    args = ap.parse_args()

    import jax

    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    from benchmarks import (bench_barrier, bench_collectives, bench_fabric,
                            bench_p2p, bench_reduce, bench_serve, bench_spmv)
    modules = {
        "p2p": (bench_p2p, "paper Fig.3: p2p latency/bandwidth"),
        "barrier": (bench_barrier, "paper Fig.4: barrier latency"),
        "reduce": (bench_reduce, "paper Fig.5: reduce latency"),
        "spmv": (bench_spmv, "paper Fig.6: PETSc MatMult (27pt stencil)"),
        "collectives": (bench_collectives,
                        "beyond-paper: hierarchical vs flat grad sync, "
                        "Comm-API schedules"),
        "serve": (bench_serve,
                  "beyond-paper: continuous vs static serving on a "
                  "mixed-arrival trace (DESIGN.md §8)"),
        "fabric": (bench_fabric,
                   "beyond-paper: multi-rank serving fabric (replicated "
                   "vs disaggregated placement, KV-block migration — "
                   "DESIGN.md §10)"),
    }
    if args.only:
        modules = {args.only: modules[args.only]}

    print("name,us_per_call,derived")
    failures = 0
    sections = {}
    for key, (mod, desc) in modules.items():
        print(f"# --- {key}: {desc} ---")
        try:
            rows = list(mod.rows(fast=args.fast))
        except Exception:
            failures += 1
            traceback.print_exc()
            continue
        sections[key] = [
            {"name": name, "us_per_call": us, "derived": derived}
            for name, us, derived in rows]
        for name, us, derived in rows:
            print(f"{name},{us:.3f},{derived}")

    if args.json:
        payload = {
            "schema": "repro-bench-v1",
            "fast": args.fast,
            "platform": {"python": platform.python_version(),
                         "machine": platform.machine()},
            # the device this process's rows ran on; rows measured in a
            # subprocess on virtual CPU devices say so in their own
            # ``derived`` field (benchmarks.common.run_mp_case)
            "device": {"platform": jax.devices()[0].platform,
                       "kind": jax.devices()[0].device_kind,
                       "count": len(jax.devices())},
            "sections": sections,
            "failures": failures,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {args.json} "
              f"({sum(len(v) for v in sections.values())} rows)")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
