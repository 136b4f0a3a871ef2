"""Benchmark helpers: wall timing + multi-device subprocess execution.

The main bench process keeps the single real CPU device (per the dry-run
isolation rule); collective benchmarks run named cases from
benchmarks/mp_bench.py in a subprocess with N virtual CPU devices and emit
``ROW,<name>,<us>,<derived>`` lines that the parent collects. Those rows
are CPU measurements whatever device the parent holds, and each one says
so in its ``derived`` field.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import List, Tuple

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

Row = Tuple[str, float, str]


def time_fn(fn, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median wall time (us) of fn(*args) with block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def run_mp_case(case: str, ndev: int = 8, timeout: int = 900,
                args=()) -> List[Row]:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, env.get("PYTHONPATH", "")])
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.mp_bench", case, *map(str, args)],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=ROOT)
    if proc.returncode != 0:
        raise RuntimeError(f"bench case {case} failed:\n{proc.stdout}\n"
                           f"{proc.stderr}")
    rows = []
    for line in proc.stdout.splitlines():
        if line.startswith("ROW,"):
            _, name, us, derived = line.split(",", 3)
            rows.append((name, float(us),
                         f"{derived} [device=cpu x{ndev} virtual]"))
    return rows


def emit(rows: List[Row]):
    for name, us, derived in rows:
        print(f"{name},{us:.3f},{derived}")
