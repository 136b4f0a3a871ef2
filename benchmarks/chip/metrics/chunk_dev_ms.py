"""Model step: device ms per execution of the engine's chunk program in
the traced span, averaged over chips."""

from benchmarks.chip.metrics._device import device_ms


def read(run):
    return device_ms(run, "chunk")
