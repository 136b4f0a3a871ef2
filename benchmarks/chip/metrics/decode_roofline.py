"""Kernels (the jnp ops of the decode program): the least time the chip
could take for the decode work the traffic needs (work.py, peaks.json) over
the measured device time per execution (%)."""

from benchmarks.chip.metrics._device import roofline


def read(run):
    return roofline(run, "decode")
