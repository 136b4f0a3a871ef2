"""Spans on the profiler's clock (DESIGN.md §15).

One helper, :func:`span`, wraps ``jax.profiler.TraceAnnotation``. Under
a profiler session (``jax.profiler.trace``, ``launch/serve.py
--trace-out``, the chip benchmark's traced window) each span is an event
of the profile's host plane, on the same timeline as the device's
operations, with its keyword arguments as the event's arguments. With no
session the annotation records nothing and costs about a microsecond, so
spans have no switch. A span also reads ``perf_counter`` at both ends:
``phases=`` adds its wall time to a dict, which the engine keeps for its
latest step whether or not a profiler runs.

Rank attribution is the ``rank=`` argument of the fabric's rank-step
span; inside it the thread's rank is also what the residual ledger
charges.

The residual ledger (:class:`~repro.obs.residuals.ResidualLedger`) is
opt-in state, on under ``REPRO_TRACE=1`` or :func:`install`. A span marked
with :meth:`Span.hop` is a priced hop: at its end the ledger records the
modeled price against the span's measured time, and the span carries
``hop``, ``modeled_s`` and ``measured_s``. :func:`on_wait` feeds the
serialization-stall detector from ``Request.wait``.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, Optional

from jax.profiler import TraceAnnotation

from repro.obs.residuals import ResidualLedger

_tls = threading.local()


def current_rank() -> Optional[int]:
    """The rank whose step this thread is running (``rank=`` of the
    innermost enclosing span that set one), else None."""
    return getattr(_tls, "rank", None)


class Span:
    """A host span: a ``TraceAnnotation`` that also times itself. Use it
    as a context manager; a handle entered by hand must reach
    :meth:`end` (the ``span-leak`` lint rule checks that)."""

    __slots__ = ("name", "t0", "seconds", "_ann", "_phases", "_hop",
                 "_rank", "_outer_rank")

    def __init__(self, name: str, phases: Optional[Dict[str, float]] = None,
                 **args):
        self.name = name
        self.seconds = 0.0
        self._ann = TraceAnnotation(name, **args)
        self._phases = phases
        self._hop = None
        self._rank = args.get("rank")

    def __enter__(self) -> "Span":
        if self._rank is not None:
            self._outer_rank = current_rank()
            _tls.rank = self._rank
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def set(self, **args) -> None:
        """Arguments known only once the span's work has run."""
        self._ann.set_metadata(**args)

    def hop(self, kind: str, modeled_s: float) -> None:
        """Price this span as a hop of ``kind`` (the §3.2 model's cost)."""
        self._hop = (kind, float(modeled_s))

    def __exit__(self, *exc) -> bool:
        self.seconds = time.perf_counter() - self.t0
        if self._hop is not None and (not exc or exc[0] is None):
            kind, modeled = self._hop
            led = _LEDGER
            if led is not None:
                led.record(kind, modeled, self.seconds, rank=current_rank())
            self._ann.set_metadata(hop=kind, modeled_s=modeled,
                                   measured_s=self.seconds)
        self._ann.__exit__(*exc)
        if self._rank is not None:
            _tls.rank = self._outer_rank
        if self._phases is not None:
            ph = self._phases
            ph[self.name] = ph.get(self.name, 0.0) + self.seconds
        return False

    def end(self) -> None:
        self.__exit__(None, None, None)


span = Span


def set_runnable(n: int) -> None:
    """Thread-local runnable-work hint for the stall detector: the live
    rows and queued requests this thread could be advancing now."""
    _tls.runnable = int(n)


def on_wait(seconds: float) -> None:
    """A comm completion blocked this thread for ``seconds``: charged to
    the stall detector when the ledger is on and the thread had runnable
    work."""
    led = _LEDGER
    if led is not None and getattr(_tls, "runnable", 0) > 0:
        led.stall(seconds, rank=current_rank())


# ---------------------------------------------------------------------------
# The ledger's activation — the sanitizer's pattern (DESIGN.md §11): one
# module global, None when off.
# ---------------------------------------------------------------------------

_LEDGER: Optional[ResidualLedger] = None


def ledger() -> Optional[ResidualLedger]:
    return _LEDGER


def install() -> ResidualLedger:
    global _LEDGER
    _LEDGER = ResidualLedger()
    return _LEDGER


def uninstall() -> None:
    global _LEDGER
    _LEDGER = None


def flush_trial() -> None:
    """Trial boundary (post-warm-up reset / fabric close): drop the
    residual pairs and stall time so compile-heavy warm-up never counts
    in a measured trial. A no-op when the ledger is off."""
    led = _LEDGER
    if led is not None:
        led.reset()


if os.environ.get("REPRO_TRACE", "").strip().lower() in ("1", "true", "yes",
                                                         "on"):
    install()
