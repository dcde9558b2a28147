"""`repro.obs` — the zero-dependency telemetry plane.

Two halves: :mod:`repro.obs.trace` (context-manager spans with a
thread-local active stack, collected into exportable span trees) and
:mod:`repro.obs.metrics` (a process-global registry of counters / gauges /
histograms). :mod:`repro.obs.report` exports both as JSON / text and
cross-checks measured kernel-launch counts against the roofline analytic
model.

Off by default: ``enable()`` flips the tracing flag *and* subscribes the
launch-event hook in ``kernels/roaring/ops.py`` so every kernel dispatch
increments ``roaring.launches{entry,backend}`` and lands as an event on the
innermost open span. ``disable()`` undoes both. The hook fires at Python
dispatch time: an eager dispatch counts once per call, a dispatch inside a
``jax.jit`` program once per trace of that program, never per call. Spans
count calls: the search service opens one ``search.execute`` per batch
program and one ``search.score`` per scoring. The metrics registry itself
has no switch — bare-int counters (ladder failures, cache hits) are cheap
enough to stay always-on — but instrumentation sites that cost real work
(host syncs for kind histograms, gauge refreshes, span bookkeeping) gate on
``enabled()``.
"""

from __future__ import annotations

from contextlib import contextmanager

from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               publish_service_gauges, registry,
                               reset_metrics)
from repro.obs.report import (collect, environment, launch_crosscheck,
                              render_text, write_report)
from repro.obs.trace import (Span, current_span, record_span,
                             reset_traces, span, span_trees, tracing)
from repro.obs import trace as _trace

__all__ = [
    # switches
    "enable", "disable", "enabled", "telemetry_scope",
    # tracing
    "Span", "span", "record_span", "current_span", "span_trees",
    "reset_traces",
    # metrics
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "registry",
    "reset_metrics", "record_kinds", "KIND_NAMES",
    "publish_service_gauges",
    # reporting
    "collect", "write_report", "render_text", "launch_crosscheck",
    "environment",
]

KIND_NAMES = ("empty", "array", "bitmap", "run")

_HOOKED = False


def _on_launch(ev) -> None:
    """Launch-hook subscriber: count the dispatch and pin it to the
    innermost open span as an event."""
    registry().counter("roaring.launches",
                       entry=ev.entry, backend=ev.backend).inc()
    sp = current_span()
    if sp is not None:
        sp.add_event("launch", entry=ev.entry, backend=ev.backend)


def enable() -> None:
    """Turn telemetry on: record spans and subscribe the kernel launch
    hook. Idempotent."""
    global _HOOKED
    _trace.set_tracing(True)
    if not _HOOKED:
        from repro.kernels.roaring import ops as kops
        kops.add_launch_hook(_on_launch)
        _HOOKED = True


def disable() -> None:
    """Turn telemetry off (the default). Collected spans/metrics are kept
    until ``reset_traces()`` / ``reset_metrics()``."""
    global _HOOKED
    _trace.set_tracing(False)
    if _HOOKED:
        from repro.kernels.roaring import ops as kops
        kops.remove_launch_hook(_on_launch)
        _HOOKED = False


def enabled() -> bool:
    """Whether telemetry is currently on."""
    return _trace.tracing()


@contextmanager
def telemetry_scope(on: bool = True):
    """Temporarily force telemetry on (default) or off, restoring the
    previous state on exit — e.g. ``with telemetry_scope(): store.query(p)``
    or ``with telemetry_scope(on=False):`` around a timing window."""
    was = enabled()
    (enable if on else disable)()
    try:
        yield
    finally:
        (enable if was else disable)()


def record_kinds(name: str, kinds) -> None:
    """Bump per-container-kind counters (``<name>{kind=...}``) from a kinds
    vector. Safe to call from instrumented paths that may run under
    ``jax.jit`` tracing: tracers (no concrete values) are skipped, and the
    host sync only happens while telemetry is enabled."""
    if not enabled():
        return
    try:
        import jax
        import numpy as np
        if isinstance(kinds, jax.core.Tracer):
            return
        counts = np.bincount(
            np.asarray(kinds).astype(np.int64).ravel(),
            minlength=len(KIND_NAMES))
    except Exception:
        return
    reg = registry()
    for i, kname in enumerate(KIND_NAMES):
        n = int(counts[i]) if i < counts.size else 0
        if n:
            reg.counter(name, kind=kname).inc(n)
