"""The traced window by the program's own spans and the model's scopes.

Extends the two stages of :mod:`trace_reduce`, whose keys and values it
keeps unchanged:

1. :func:`events` adds the program's host spans (``serve.*``,
   ``fabric.*``, ``comm.*``: ``repro.obs.trace``) and, for each program,
   a map from device op to the named scope it was traced under
   (``attention``, ``mlp``, ``lm_head``, ``sample``...). A TPU profile's
   op events carry the HLO instruction's name and none of its metadata,
   so the map comes from the ``op_name`` metadata of the program's
   optimized HLO (``ContinuousEngine.program_text``); a fusion carries
   its root op's.
2. :func:`reduce` adds ``scopes`` (device seconds per execution, per
   program and scope; ops under no scope are ``unscoped``, ops the HLO
   text does not name ``unmapped``), ``phases``
   (for each ``serve.step`` in the window, the seconds of each program
   span inside it, the step's own included) and ``idle_phases`` (device
   idle time by the innermost program span it falls in).

A trace without program spans or scope maps reduces to exactly what
:func:`trace_reduce.reduce` gives.

As a script, it runs one traced window of a cell and prints the
reduction with the per-layer readings that read it:

    python3 benchmarks/chip/trace_scopes.py --workload <name> \\
        --seed <n> [--seconds <s>]
"""

from __future__ import annotations

import bisect
import heapq
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional

if __name__ == "__main__":
    _ROOT = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(_ROOT / "src"), str(_ROOT)]

from benchmarks.chip import trace_reduce as T  # noqa: E402

PROGRAM_PREFIXES = ("serve.", "fabric.", "comm.")
#: the model's named scopes; an op takes the innermost one on its path
SCOPES = ("layers", "embed", "norm", "attention", "mlp", "moe", "lm_head",
          "sample")
UNSCOPED = "unscoped"
#: a device op that the program's HLO text does not name
UNMAPPED = "unmapped"
STEP = "serve.step"
OUTSIDE = "outside the program spans"

_INSTRUCTION = re.compile(r'^\s*(?:ROOT )?%(\S+) = (.*)$', re.M)
_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')


def scope_of(op_name: str) -> str:
    """The innermost model scope on an ``op_name`` path."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """Each instruction of an optimized HLO module, by name, to its scope
    (``unscoped`` where its ``op_name`` metadata names none, or it has
    none)."""
    out = {}
    for name, rest in _INSTRUCTION.findall(hlo_text):
        op = _OP_NAME.search(rest)
        out[name] = scope_of(op.group(1)) if op else UNSCOPED
    return out


def events(xplane_path: str, program_text: Optional[Dict[str, str]] = None
           ) -> dict:
    """Stage 1: :func:`trace_reduce.events` plus ``spans`` (``[name,
    start_ns, duration_ns, thread]`` of every program span) and
    ``op_scopes`` (jitted name -> op -> scope) from ``program_text``
    (jitted name -> optimized HLO text)."""
    from jax.profiler import ProfileData

    out = T.events(xplane_path)
    spans = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name != "/host:CPU":
            continue
        # host lines are threads; their names repeat ("python"), their
        # places do not
        for thread, line in enumerate(plane.lines):
            spans.extend([e.name, e.start_ns, e.duration_ns, thread]
                         for e in line.events
                         if e.name.startswith(PROGRAM_PREFIXES))
    out["spans"] = spans
    out["op_scopes"] = {fn: op_scopes(text)
                        for fn, text in (program_text or {}).items()}
    return out


def reduce(ev: dict, programs: Dict[str, str]) -> Optional[dict]:
    """Stage 2: :func:`trace_reduce.reduce` plus ``scopes`` where the
    trace has scope maps and ``phases`` / ``idle_phases`` where it has
    program spans."""
    r = T.reduce(ev, programs)
    if r is None:
        return None
    lo, hi = next((s, s + d) for n, s, d in ev["host"] if n == T.WINDOW)
    if ev.get("op_scopes"):
        r["scopes"] = _scopes(ev, programs, lo, hi, r["programs"])
    spans = [(s, s + d, n, t) for n, s, d, t in ev.get("spans", ())
             if s + d > lo and s < hi]
    if spans:
        r["phases"] = _phases(spans, lo, hi)
        r["idle_phases"] = _idle_phases(ev, spans, lo, hi)
    return r


def _scopes(ev, programs, lo, hi, execs) -> Dict[str, Dict[str, float]]:
    """Device seconds per execution of each program, by scope: each op's
    self time, as :func:`trace_reduce.reduce` counts it."""
    key_of = {fn: key for key, fn in programs.items()}
    total: Dict[str, Dict[str, float]] = {k: defaultdict(float)
                                          for k in programs}
    for dev in ev["devices"].values():
        mods = sorted((s, s + d, T._short_module(n))
                      for n, s, d in dev["modules"] if lo <= s < hi)
        starts = [m[0] for m in mods]
        ops = [(s, s + d, n) for n, s, d in dev["ops"]]
        for s, e, n, own in T._self_times(T._clip_named(ops, lo, hi)):
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= mods[i][1] or mods[i][2] not in key_of:
                continue
            fn = mods[i][2]
            scope = ev["op_scopes"].get(fn, {}).get(T._short_op(n), UNMAPPED)
            total[key_of[fn]][scope] += own * 1e-9
    return {k: {sc: v / len(execs[k]) for sc, v in sorted(
        by.items(), key=lambda kv: -kv[1])}
        for k, by in total.items() if execs.get(k)}


def _phases(spans, lo, hi) -> List[Dict[str, float]]:
    """For each ``serve.step`` inside the window: seconds in each program
    span on its thread within it, the step's own duration under
    ``serve.step``."""
    by_thread = defaultdict(list)
    for s, e, n, t in spans:
        by_thread[t].append((s, e, n))
    out = []
    for t, sp in by_thread.items():
        sp.sort()
        starts = [x[0] for x in sp]
        for s, e, n in sp:
            if n != STEP or s < lo or e > hi:
                continue
            step: Dict[str, float] = defaultdict(float)
            for s2, e2, n2 in sp[bisect.bisect_left(starts, s):
                                 bisect.bisect_right(starts, e)]:
                if e2 <= e:
                    step[n2] += (e2 - s2) * 1e-9
            out.append((s, dict(step)))
    return [p for _, p in sorted(out, key=lambda x: x[0])]


def _innermost(spans) -> List[tuple]:
    """Disjoint ``(start, end, name)`` segments: at each time the program
    span that started last among those still open (the innermost where
    spans nest; across threads, the latest to open)."""
    spans = sorted(spans)
    points = sorted({p for s, e, _, _ in spans for p in (s, e)})
    heap: List[tuple] = []
    out: List[tuple] = []
    j = 0
    for a, b in zip(points, points[1:]):
        while j < len(spans) and spans[j][0] <= a:
            s, e, n, _ = spans[j]
            heapq.heappush(heap, (-s, e, n))
            j += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if heap:
            name = heap[0][2]
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def _idle_phases(ev, spans, lo, hi) -> List[list]:
    """Device idle time in the window by the innermost program span it
    falls in, mean over chips."""
    segs = _innermost(spans)
    seg_starts = [s for s, _, _ in segs]
    idle = defaultdict(float)
    for dev in ev["devices"].values():
        merged = T._union(T._clip([(s, s + d) for _, s, d in dev["ops"]],
                                  lo, hi))
        edge = lo
        for s, e in merged + [[hi, hi]]:
            if s > edge:
                _charge(edge, s, segs, seg_starts, idle)
            edge = max(edge, e)
    n = len(ev["devices"])
    return [[k, v / n] for k, v in sorted(idle.items(), key=lambda kv: -kv[1])]


def _charge(s, e, segs, seg_starts, idle) -> None:
    covered = 0.0
    i = max(bisect.bisect_right(seg_starts, s) - 1, 0)
    while i < len(segs) and segs[i][0] < e:
        a, b, name = segs[i]
        ov = min(e, b) - max(s, a)
        if ov > 0:
            idle[name] += ov * 1e-9
            covered += ov
        i += 1
    if covered < e - s:
        idle[OUTSIDE] += (e - s - covered) * 1e-9


# ---------------------------------------------------------------------------
# One traced window, reduced and read
# ---------------------------------------------------------------------------

#: per-layer readings of this reduction (``metrics/<name>.py``)
READINGS = ("sample_dev_ms", "attn_dev_ms", "step_host_ms")


def traced_window(cell, seed: int, seconds: float,
                  trace_dir: Optional[Path] = None) -> dict:
    """Warm-in, then a traced window of ``seconds``, as ``run.py --trace
    1`` traces the end of its window; returns the reduction, the readings
    of this module's readers and of the cell's, and what was traced."""
    import shutil
    import time
    from types import SimpleNamespace

    import jax

    from benchmarks.chip import drive, run, spec, work

    trace_dir = str(trace_dir or run.TRACE_DIR)
    settings, config = cell.settings, cell.config
    vocab = int(config["config"]["vocab_size"])
    arch = cell.arch
    model = drive.program_model(arch.program_config(config))
    params = drive.draw_weights(model, seed, arch)
    eng = drive.make_engine(model, params, config, settings["serving"])
    drive.warm_up(eng, vocab)
    # the profiler's first start costs seconds on a TPU: pay it here
    jax.profiler.start_trace(trace_dir)
    jax.profiler.stop_trace()
    shutil.rmtree(trace_dir, ignore_errors=True)
    drv = drive.Driver(eng, cell.mix, seed, vocab,
                       annotate=jax.profiler.TraceAnnotation)
    drv.start()
    drv.run_until(drv.clock() + float(settings["warm_in_s"]))
    with drive.no_compiles("traced window"):
        jax.profiler.start_trace(trace_dir)
        drv.record_work = True
        t0 = drv.clock()
        with jax.profiler.TraceAnnotation(T.WINDOW):
            t1 = drv.run_until(t0 + seconds)
        jax.profiler.stop_trace()
    t_text = time.perf_counter()
    text = eng.program_text()
    t_text = time.perf_counter() - t_text
    ev = events(T.find_xplane(trace_dir), text)
    shutil.rmtree(trace_dir, ignore_errors=True)
    reduced = reduce(ev, settings["programs"])
    dev = jax.devices()[0]
    view = SimpleNamespace(
        records=drv.records, steps=drv.steps, work=drv.work, t0=t0, t1=t1,
        trace=reduced, counts=arch.counts(config),
        peaks=(work.load_peaks(dev.device_kind) if dev.platform == "tpu"
               else None),
        peak_blocks=drv.peak_blocks, pool_blocks=eng.kv.pool.num_blocks)
    readers = {**{n: spec.reader(n) for n in READINGS}, **cell.readers}
    return {"device": dev.device_kind, "seed": seed,
            "program_text_s": t_text,
            "traced": {"spans": len(ev["spans"]),
                       "scoped_ops": {fn: len(m)
                                      for fn, m in ev["op_scopes"].items()}},
            "readings": {n: read(view) for n, read in readers.items()},
            "reduced": {k: v for k, v in (reduced or {}).items()
                        if k not in ("programs", "phases")},
            "executions": {k: len(v) for k, v in
                           (reduced or {}).get("programs", {}).items()},
            "phases": (reduced or {}).get("phases")}


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)

    import jax

    from benchmarks.chip import spec
    from repro.launch.cache import use_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("trace_scopes.py: needs a TPU", file=sys.stderr)
        return 1
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = traced_window(spec.cell(args.workload), args.seed, args.seconds)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
