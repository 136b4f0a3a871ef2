"""Reduce a ``jax.profiler`` trace of the traced window to numbers.

Two stages, so that the second can be checked on a small recorded trace:

1. :func:`events` reads the ``.xplane.pb`` with nothing but JAX: for each
   TPU device plane its ``XLA Modules`` (one event per program execution,
   named ``jit_<function>(<fingerprint>)``) and ``XLA Ops``; from the host
   plane the harness's own ``TraceAnnotation`` spans.
2. :func:`reduce` takes the span named ``bench.window`` as the window and
   gives device busy time (the union of op intervals) per chip, device
   seconds per execution of each named program, the operations that took
   most time, and the idle time by what the host was doing meanwhile.

Device and host timestamps come from one profile and agree to about a
millisecond, which bounds how finely idle time can be attributed.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional

WINDOW = "bench.window"
HOST_PREFIXES = ("bench.", "engine.", "traffic.")
TOP = 10


def events(xplane_path: str) -> dict:
    """Stage 1: the events the reduction needs, as plain JSON-able data
    (times in nanoseconds on the profile's clock)."""
    from jax.profiler import ProfileData

    out = {"devices": {}, "host": []}
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(line.name)
                if key:
                    dev[key] = [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events]
            out["devices"][plane.name.rsplit(":", 1)[-1]] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out["host"].extend(
                    [e.name, e.start_ns, e.duration_ns] for e in line.events
                    if e.name.startswith(HOST_PREFIXES))
    return out


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _short_op(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%").strip()


def _short_module(name: str) -> str:
    name = name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def reduce(ev: dict, programs: Dict[str, str]) -> Optional[dict]:
    """Stage 2. ``programs`` maps a short name (``decode``) to the jitted
    function's name (``_decode_traced``). None when the trace holds no
    window or no device."""
    win = [(s, s + d) for n, s, d in ev["host"] if n == WINDOW]
    if not win or not ev["devices"]:
        return None
    lo, hi = win[0]
    span_s = (hi - lo) * 1e-9
    busy, idle_by = [], defaultdict(float)
    execs: Dict[str, List[float]] = {k: [] for k in programs}
    op_time: Dict[str, float] = defaultdict(float)
    spans = sorted((s, s + d, n) for n, s, d in ev["host"]
                   if n != WINDOW and s + d > lo and s < hi)
    host = ([h[0] for h in spans], spans)
    for dev in ev["devices"].values():
        mods = sorted((s, s + d, n) for n, s, d in dev["modules"]
                      if lo <= s < hi)
        for s, e, n in mods:
            fn = _short_module(n)
            for key, want in programs.items():
                if fn == want:
                    execs[key].append((e - s) * 1e-9)
        starts = [m[0] for m in mods]
        ops = [(s, s + d, n) for n, s, d in dev["ops"]]
        for s, e, n, own in _self_times(_clip_named(ops, lo, hi)):
            i = bisect.bisect_right(starts, s) - 1
            mod = _short_module(mods[i][2]) if i >= 0 and s < mods[i][1] \
                else "?"
            op_time[f"{mod}:{_short_op(n)}"] += own * 1e-9
        merged = _union(_clip([(s, e) for s, e, _ in ops], lo, hi))
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edge = lo
        for s, e in merged + [[hi, hi]]:
            if s > edge:
                _attribute(edge, s, host, idle_by)
            edge = max(edge, e)
    n = len(ev["devices"])
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(((k, v / n) for k, v in idle_by.items()),
                  key=lambda kv: -kv[1])[:TOP]
    return {"window_s": span_s, "busy_s": sum(busy) / n,
            "programs": execs, "chips": n,
            "device_ops": [[k, v / n] for k, v in top],
            "idle_gaps": [[k, v] for k, v in gaps]}


def _self_times(ops):
    """``(start, end, name, self time)`` of each op: a loop or call op
    (``while``) holds the ops of its body, whose time is its own, not the
    loop's."""
    out = []
    stack = []                      # indices into ``out`` of open ops
    for s, e, n in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and out[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= out[stack[-1]][1]:
            out[stack[-1]][3] -= e - s
        out.append([s, e, n, e - s])
        stack.append(len(out) - 1)
    return out


def _clip_named(intervals, lo, hi):
    return [(max(s, lo), min(e, hi), n) for s, e, n in intervals
            if e > lo and s < hi]


def _attribute(s: float, e: float, host, idle_by) -> None:
    """Charge the idle interval [s, e] to the host spans overlapping it
    (the harness's spans follow one another on one thread, so they do not
    overlap); what none covers is charged to the rest of the host."""
    starts, spans = host
    covered = 0.0
    i = bisect.bisect_left(starts, e) - 1
    while i >= 0 and spans[i][1] > s:
        hs, he, name = spans[i]
        ov = min(e, he) - max(s, hs)
        if ov > 0:
            idle_by[name] += ov * 1e-9
            covered += ov
        i -= 1
    if covered < e - s:
        idle_by["host outside the harness spans"] += (e - s - covered) * 1e-9

