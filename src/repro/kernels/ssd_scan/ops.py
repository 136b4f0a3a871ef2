"""jit'd wrapper for the SSD scan kernel."""

from __future__ import annotations

import functools

import jax

from repro.kernels.ssd_scan.ssd_scan import ssd_scan_fwd


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret", "return_state"))
def ssd_scan(x, dt, A, Bm, Cm, initial_state=None, *, chunk: int = 128,
             interpret: bool = False, return_state: bool = False):
    """See ssd_scan_fwd. Oracle: ref.ssd_scan_ref (sequential recurrence).

    ``initial_state``/``return_state`` thread the carried scan state
    across calls — the kernel-level contract behind state-threaded
    chunked prefill (DESIGN.md §13)."""
    return ssd_scan_fwd(x, dt, A, Bm, Cm, initial_state, chunk=chunk,
                        interpret=interpret, return_state=return_state)
