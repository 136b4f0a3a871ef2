"""Run one benchmark cell once and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

In one process on the cell's chips, in this order: refuse a host without
a TPU (or with fewer chips than the cell asks for); draw the weights on
the device from the seed; warm up the engine's programs (JAX's compile
cache lives in the checkout, ``.jax_cache``, unless
``JAX_COMPILATION_CACHE_DIR`` says otherwise); warm in the traffic;
measure for ``--seconds`` with no compile allowed; check the served tokens
against the plain reference; print the result as the last line of
standard output, after the compared numbers on standard error.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
the last 4 s of the window with ``jax.profiler`` and
reports its per-layer metrics instead.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()      # set-up runs from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
# the TPU runtime would log to a fixed /tmp path; a run writes nothing
# outside its checkout and the directories it is given
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: the stretch at the end of the window that one traced run records
TRACE_SECONDS = 4.0
TRACE_DIR = ROOT / ".bench_trace"


def run_cell(cell, seed: int, seconds: float, trace: bool,
             t_process: float = T_PROCESS, control: str | None = None) -> dict:
    """One run of ``cell`` on the default device; returns the result.
    With ``control`` (``"fp8"``) the control takes the program's place in
    the verdict: ``correct`` and ``checks`` judge the control's gaps on
    the same sample by the cell's own rule, and ``result["gaps"]`` holds
    the widest and the mean gap of the program and of the control."""
    import jax

    from benchmarks.chip import check, drive, e2e, trace_reduce, work

    settings, config = cell.settings, cell.config
    vocab = int(config["config"]["vocab_size"])
    model = drive.program_model(config)
    params = drive.draw_weights(model, seed)
    eng = drive.make_engine(model, params, config, settings["serving"])
    drive.warm_up(eng, vocab)
    # everything set-up made lives to the end: keep it out of the
    # collector's full passes, as a long-running server does
    gc.collect()
    gc.freeze()

    if trace:
        # the profiler's first start costs seconds on a TPU: pay it here
        jax.profiler.start_trace(str(TRACE_DIR))
        jax.profiler.stop_trace()
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    annotate = jax.profiler.TraceAnnotation if trace else None
    drv = drive.Driver(eng, cell.mix, seed, vocab, annotate=annotate)
    clock = drv.clock
    drv.start()
    drv.run_until(clock() + float(settings["warm_in_s"]))

    t0 = clock()
    setup_s = t0 - t_process
    drv.mark_window_start()
    reduced = None
    pauses = drive.GcPauses()
    with drive.no_compiles("measured window"), pauses:
        if trace:
            # the last seconds of the window; the profiler stops after it
            drv.run_until(t0 + seconds - min(TRACE_SECONDS, seconds / 2))
            jax.profiler.start_trace(str(TRACE_DIR))
            drv.record_work = True
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                t1 = drv.run_until(t0 + seconds)
        else:
            t1 = drv.run_until(t0 + seconds)
    drv.mark_window_end()
    if trace:
        jax.profiler.stop_trace()

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    if trace:
        reduced = trace_reduce.reduce(
            trace_reduce.events(trace_reduce.find_xplane(str(TRACE_DIR))),
            settings["programs"])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    host = drv.host_stats(t0, t1)
    host["gc_pauses"], host["gc_longest_ms"] = len(pauses.seconds), 1e3 * max(
        pauses.seconds, default=0.0)
    window = e2e.window_metrics(drv.records, t0, t1)
    window["setup_s"] = setup_s
    metrics = {}
    breakdown = None
    if not trace:
        for m in cell.end_to_end:
            if window.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": window[m["name"]],
                                      "unit": m["unit"]}
    else:
        view = SimpleNamespace(
            records=drv.records, steps=drv.steps, work=drv.work,
            t0=t0, t1=t1, trace=reduced, dims=work.Dims.of(config),
            peaks=work.load_peaks(dev.device_kind) if dev.platform == "tpu"
            else None, peak_blocks=drv.peak_blocks,
            pool_blocks=eng.kv.pool.num_blocks)
        for m in cell.per_layer:
            value = cell.readers[m["name"]](view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}

    # the engine's pool and state go before the reference runs
    finish = {r.rid: r.finish for r in drv.records}
    finished = ([r for r in drv.finished if finish[r.rid] >= t0]
                or list(drv.finished))
    for buf in jax.tree_util.tree_leaves(eng.kv.buffers):
        buf.delete()
    del drv, eng
    gc.unfreeze()
    gc.collect()

    rule = settings["check"]
    picked = check.sample(finished, seed, rule)
    t_ref = time.perf_counter()
    stats = check.gap_stats(config, params, picked, rule) if picked else None
    t_ref = time.perf_counter() - t_ref
    judged = stats
    if control and picked:
        judged = check.gap_stats(config, params, picked, rule, quant=control)
    limit = float(rule["limit"])
    gap = judged[rule["statistic"]] if judged else None
    result = {"correct": gap is not None and gap <= limit,
              "attempted": window["attempted"], "failed": window["failed"],
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control and picked:
        result["gaps"] = {"program": stats, "control": judged}
    result["host"] = host
    result["checked"] = {"requests": len(picked),
                         "tokens": check.checked_tokens(picked, rule),
                         "reference_s": t_ref}
    # the compared numbers, each beside its limit, come last
    result["checks"] = {f"{rule['statistic']}_logit_gap": {"value": gap,
                                                          "limit": limit}}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"run.py: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 2
    from benchmarks.chip import spec
    cell = spec.cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    checks = result["checks"]
    print("host: " + ", ".join(f"{k} {v}" for k, v in result["host"].items()),
          file=sys.stderr)
    done = result["checked"]
    print(f"checked {done['requests']} requests, {done['tokens']} served "
          f"tokens against the reference in {done['reference_s']:.1f} s",
          file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
