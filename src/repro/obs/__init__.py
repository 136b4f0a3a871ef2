"""Serving-fabric observability (DESIGN.md §15): host spans on the
profiler's clock, a metrics registry, and modeled-vs-measured cost
residuals.

Spans (:mod:`repro.obs.trace`) are ``jax.profiler`` annotations: always
emitted, recorded only under a profiler session. The push-metrics
registry (:mod:`repro.obs.metrics`) and the residual ledger
(:mod:`repro.obs.residuals`) are state, off by default: one module-global
read plus a ``None`` check when off, the sanitizer's pattern (DESIGN.md
§11). ``REPRO_TRACE=1`` (or :func:`install`) turns both on.
"""

from __future__ import annotations

from repro.obs import metrics, residuals, trace
from repro.obs.metrics import MetricsRegistry
from repro.obs.residuals import ResidualLedger, merge_reports
from repro.obs.trace import Span, span


def install() -> ResidualLedger:
    """Turn on the registry and the residual ledger; returns the ledger.
    Equivalent to launching under ``REPRO_TRACE=1``."""
    metrics.install()
    return trace.install()


def uninstall() -> None:
    trace.uninstall()
    metrics.uninstall()


def flush_trial() -> None:
    """Trial-boundary flush (residual ledger + push registry); wired
    into ``ContinuousEngine.reset`` and ``ServingFabric.close`` so warm
    trials never aggregate into measured ones. No-op when off."""
    trace.flush_trial()
    metrics.flush_trial()


__all__ = [
    "MetricsRegistry", "ResidualLedger", "Span", "flush_trial", "install",
    "merge_reports", "metrics", "residuals", "span", "trace", "uninstall",
]
