"""Shared arithmetic of the device-trace readers: the work the harness
saw dispatched in the traced span, against the device time of the
program's executions there."""

from benchmarks.chip import work as W


def dispatches(run, program):
    """(FLOPs, bytes) of each ``program`` dispatch in the traced span."""
    out = []
    for step in run.work:
        if program == "decode" and step.decode_ctx:
            out.append(W.decode_work(run.dims, step.decode_ctx))
        elif program == "chunk" and step.chunk_rows:
            out.append(W.chunk_work(run.dims, step.chunk_rows))
    return out


def device_ms(run, program):
    """Mean device ms per execution of ``program`` in the traced span."""
    if run.trace is None or not run.trace["programs"].get(program):
        return None
    ex = run.trace["programs"][program]
    return 1e3 * sum(ex) / len(ex)


def roofline(run, program):
    """Least time of the needed work over the measured device time, per
    dispatch on average (%); None where the trace has no execution."""
    ms = device_ms(run, program)
    work = dispatches(run, program)
    if ms is None or not work or run.peaks is None:
        return None
    least = sum(W.least_time(f, b, run.peaks)[0] for f, b in work) / len(work)
    return 100.0 * least / (ms * 1e-3)
