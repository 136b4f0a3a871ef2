"""Readings that set a cell's correctness limit, on many seeds in one
process: for each seed, one run of the cell as the benchmark makes it
(its timed path, its load, its sample), with the program's logit gaps
and the control's on the same served tokens, and the verdict of the
cell's rule on each: the control in the program's place has to come out
not correct.

    python3 benchmarks/chip/control.py --workload <name> --seconds <s> \\
        --seeds <n,n,...> [--quant fp8]

The control is the plain reference with its weights in the precision
below the configuration's (float8 e4m3 for bfloat16). The limit lies
between the largest program reading and the smallest control reading
(see PERF.md). Needs the chip, like ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run  # noqa: F401  (puts the checkout and src/ on the path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--quant", default="fp8")
    args = ap.parse_args(argv)
    import jax
    from benchmarks.chip import spec
    from repro.launch.cache import use_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("control.py: needs a TPU", file=sys.stderr)
        return 1
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = spec.cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        r = run.run_cell(cell, seed, args.seconds, False,
                         t_process=time.perf_counter(), control=args.quant)
        rule = cell.settings["check"]
        gaps = r.get("gaps") or {}
        program = (gaps.get("program") or {}).get(rule["statistic"])
        print(json.dumps({"seed": seed,
                          "program_correct": program is not None
                          and program <= float(rule["limit"]),
                          "control_correct": r["correct"],
                          "gaps": gaps,
                          "checked": r["checked"],
                          "metrics": {k: v["value"]
                                      for k, v in r["metrics"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
