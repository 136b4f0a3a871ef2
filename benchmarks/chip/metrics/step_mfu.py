"""Whole step: model FLOPs that the traffic needed in the traced span
(work.py: routed experts only, causal attention) over the span's length
times the chip's peak (%). Idle time counts against it."""

from benchmarks.chip.metrics._device import dispatches


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    flops = sum(f for f, _ in dispatches(run, "decode")) + \
        sum(f for f, _ in dispatches(run, "chunk"))
    return 100.0 * flops / (run.trace["window_s"] * run.peaks["bf16_flops"]
                            * run.trace["chips"])
