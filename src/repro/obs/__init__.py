"""Unified variance-attribution observatory.

One tracer, one metrics hub, one attribution sample log — shared by
every subsystem so a cross-stream timeline and per-axis variance report
exist for any run:

* :mod:`repro.obs.span` — preallocated ring-buffer span tracer with an
  injected (SimClock-compatible) clock; live spans also annotate the
  JAX profiler's trace as ``repro.<name>``, and the tracer keeps
  cumulative counters;
* :mod:`repro.obs.sketch` — P² and mergeable log-histogram quantile
  sketches;
* :mod:`repro.obs.metrics` — Welford+sketch per (stream, stage, rung,
  batch-size) key;
* :mod:`repro.obs.export` — Chrome ``trace_event`` JSON for Perfetto;
* :mod:`repro.obs.attribution` — law-of-total-variance decomposition of
  frame latency over the paper's six axes;
* :mod:`repro.obs.dashboard` — periodic text dashboard
  (``launch/serve.py --obs``).

:class:`Observatory` bundles the pieces and is the object the engines
accept as ``obs=``.  It is pure observation: attaching one never changes
scheduling, rung choice, or replay output (the golden byte-identity test
holds with tracing on).  In wall-clock serving the scheduler, engines and
executor open live spans through :meth:`Observatory.span`; under a
replay's virtual time nothing runs for real, and the engines lay their
modeled stage times on the timeline with :meth:`Observatory.record`.
"""
from __future__ import annotations

import time
from typing import Callable, Iterable, Optional

from repro.obs.attribution import (FrameSample, VariationAttribution,
                                   attribute)
from repro.obs.dashboard import Dashboard, render_table
from repro.obs.export import (to_chrome_trace, validate_chrome_trace,
                              write_chrome_trace)
from repro.obs.metrics import MetricKey, MetricsHub, StageMetrics
from repro.obs.sketch import LatencySketch, P2Quantile
from repro.obs.span import (DEFAULT_CAPACITY, NO_SPAN, PROFILER_PREFIX,
                            LiveSpan, Span, SpanTracer)

__all__ = [
    "Observatory",
    "Span",
    "SpanTracer",
    "LiveSpan",
    "DEFAULT_CAPACITY",
    "NO_SPAN",
    "PROFILER_PREFIX",
    "P2Quantile",
    "LatencySketch",
    "MetricKey",
    "StageMetrics",
    "MetricsHub",
    "to_chrome_trace",
    "write_chrome_trace",
    "validate_chrome_trace",
    "FrameSample",
    "VariationAttribution",
    "attribute",
    "Dashboard",
    "render_table",
]


class Observatory:
    """Tracer + metrics hub + frame-sample log behind one handle."""

    def __init__(
        self,
        capacity: int = DEFAULT_CAPACITY,
        clock: Callable[[], float] = time.perf_counter,
    ) -> None:
        self.tracer = SpanTracer(capacity=capacity, clock=clock)
        self.metrics = MetricsHub()
        self.frames: list[FrameSample] = []

    # -------- clock --------
    @property
    def clock(self) -> Callable[[], float]:
        return self.tracer.clock

    def bind_clock(self, clock: Callable[[], float]) -> None:
        """Point the tracer at a different clock (the replayer binds the
        episode SimClock here so traces live on the virtual timeline)."""
        self.tracer.clock = clock

    # -------- recording --------
    def emit(self, span: Span) -> None:
        """Feed an already-recorded span to the metrics hub too."""
        if span.t1 > span.t0:
            self.metrics.observe_span(span)

    def record(self, *args, **kwargs) -> Span:
        """``tracer.record`` + metrics feed in one call."""
        span = self.tracer.record(*args, **kwargs)
        self.emit(span)
        return span

    def span(self, name: str, **tags) -> LiveSpan:
        """Context manager: ``tracer.span`` around the block, then the
        closed span fed to the metrics hub, as :meth:`record` feeds a
        measured one."""
        live = self.tracer.span(name, **tags)
        live.sink = self.emit
        return live

    def sample(self, frame: FrameSample) -> None:
        """Log one served frame for later axis attribution."""
        self.frames.append(frame)

    # -------- reports --------
    def attribution(self, frames: Optional[Iterable[FrameSample]] = None,
                    ) -> VariationAttribution:
        return attribute(self.frames if frames is None else frames)

    def chrome_trace(self, process_label: str = "repro") -> dict:
        return to_chrome_trace(self.tracer.spans(),
                               process_label=process_label)

    def write_trace(self, path: str, process_label: str = "repro") -> dict:
        return write_chrome_trace(self.tracer.spans(), path,
                                  process_label=process_label)

    def dashboard(self, **kwargs) -> Dashboard:
        return Dashboard(self.metrics, self.tracer, **kwargs)
