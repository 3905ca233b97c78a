"""Low-overhead span tracer — the single recording path for every
timing observation in the serving stack.

The paper attributes DNN inference-time variation to six axes (data,
I/O, model, runtime, hardware, end-to-end); a :class:`Span` is one timed
interval *tagged* with the axis it belongs to plus the serving context
needed to attribute it later: stream/tenant id, tick, rung, batch size,
and a pipeline ``track`` so overlapped pipelined ticks render on
parallel rows in Perfetto.

Design constraints, in order:

* **Low overhead** — recording a span is one clock read, a tuple
  construction, and a ring-buffer slot write under a lock.  The ring is
  preallocated (a fixed-length list), so the steady state allocates no
  container storage and never triggers list growth.
* **Bounded memory** — the ring keeps the most recent ``capacity``
  spans; older spans are overwritten and counted in ``dropped`` (the CI
  smoke asserts zero drops at the default capacity).
* **Deterministic under virtual time** — the clock is injected.  Under
  a ``SimClock`` every timestamp is virtual, so scenario-replay traces
  are byte-reproducible and tracing can never perturb a replay
  decision (the tracer only ever *reads* the clock).
* **On the profiler's clock too** — a live :meth:`SpanTracer.span` also
  opens a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``
  around its body.  While a profiler session runs, the profiler stamps
  it on the clock of the device planes, so device idle time can be put
  down to the program span the host was in; the ring keeps the
  interval on the tracer's own clock, read inside the same annotation.

Beside spans the tracer keeps cumulative counters (``add``/
``counters``): bytes and frames counted at the same layer boundaries.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Callable, NamedTuple, Optional

import jax

from repro.analysis.findings import AXES

__all__ = ["Span", "SpanTracer", "LiveSpan", "DEFAULT_CAPACITY",
           "PROFILER_PREFIX", "NO_SPAN"]

DEFAULT_CAPACITY = 65536
PROFILER_PREFIX = "repro."     # profiler name of a live span: repro.<name>

# what a call site enters in place of a span when no tracer is attached:
# ``with NO_SPAN if obs is None else obs.span(...)`` costs one test
NO_SPAN = contextlib.nullcontext()

# tags a live span inherits from the span enclosing it when not given:
# stream, tick, rung, batch_size, track, shard, at a top-level span's
# defaults
_TAG_DEFAULTS = ("", 0, "", 0, 0, -1)


class Span(NamedTuple):
    """One timed interval with variance-attribution tags.

    ``seq`` is assigned at *open* time (so a parent's id is known to its
    children even though parents close last); the ring holds spans in
    *close* order.  ``parent`` is the ``seq`` of the enclosing open span
    or ``-1`` at top level.  ``track`` separates overlapped pipelined
    ticks onto parallel renderer rows (tid in the Chrome trace).
    ``shard`` is the data-shard id serving the interval on a fleet mesh
    (``-1`` = not shard-specific / single-device).

    An immutable tuple: a live span builds one on the serving path, at
    a fraction of a frozen dataclass's cost.
    """

    name: str
    t0: float
    t1: float
    stream: str = ""
    tick: int = 0
    rung: str = ""
    batch_size: int = 0
    axis: str = "end_to_end"
    track: int = 0
    parent: int = -1
    seq: int = 0
    shard: int = -1

    @property
    def duration(self) -> float:
        return self.t1 - self.t0

    def to_dict(self) -> dict:
        return self._asdict()


class SpanTracer:
    """Preallocated ring buffer of :class:`Span` records.

    Usage::

        tracer = SpanTracer(clock=clock)       # SimClock-compatible
        with tracer.span("inference", axis="model", rung="two_stage",
                         fence=lambda: out):   # blocked on at exit
            out = jitted(x)
        tracer.instant("rung_switch", axis="model", stream="cam0")

    ``fence`` values (a device value, or a zero-arg callable returning
    one, evaluated at exit) are passed to ``jax.block_until_ready``
    before the interval closes, so a span around a jitted call measures
    execution, not async dispatch (the TV006 discipline); tvlint
    recognizes a fenced ``span`` context manager as a fenced timing
    site.
    """

    def __init__(
        self,
        capacity: int = DEFAULT_CAPACITY,
        clock: Callable[[], float] = time.perf_counter,
    ) -> None:
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1 (got {capacity})")
        self.capacity = capacity
        self.clock = clock
        self._ring: list[Optional[Span]] = [None] * capacity
        self._n = 0                    # spans ever recorded (close order)
        self._next_seq = 0             # ids handed out (open order)
        self._open: list[LiveSpan] = []   # stack of open live spans
        self._counters: dict[str, float] = {}
        self._lock = threading.Lock()

    # ---------------- recording ----------------
    def record(
        self,
        name: str,
        t0: float,
        t1: float,
        *,
        stream: str = "",
        tick: int = 0,
        rung: str = "",
        batch_size: int = 0,
        axis: str = "end_to_end",
        track: int = 0,
        parent: Optional[int] = None,
        shard: int = -1,
    ) -> Span:
        """Write one already-measured interval into the ring (the adapter
        entry point used by ``StageTimer`` and the engines' per-tick
        emission).  Returns the recorded span."""
        if axis not in AXES:
            raise ValueError(f"unknown axis {axis!r}; axes: {AXES}")
        with self._lock:
            seq = self._next_seq
            self._next_seq += 1
            if parent is None:
                parent = self._open[-1].seq if self._open else -1
            span = Span(name=name, t0=t0, t1=t1, stream=stream, tick=tick,
                        rung=rung, batch_size=batch_size, axis=axis,
                        track=track, parent=parent, seq=seq, shard=shard)
            self._write(span)
        return span

    def span(
        self,
        name: str,
        *,
        stream: Optional[str] = None,
        tick: Optional[int] = None,
        rung: Optional[str] = None,
        batch_size: Optional[int] = None,
        axis: str = "end_to_end",
        track: Optional[int] = None,
        shard: Optional[int] = None,
        fence: Any = None,
    ) -> "LiveSpan":
        """Context-managed span with nesting (children see this span as
        their ``parent``).  A tag left out (``None``) is inherited from
        the enclosing open span, or takes the ``Span`` default at top
        level, so a layer need not know the tick or rung it serves.
        ``fence`` — a device value or a zero-arg callable returning one
        (evaluated at exit, so it can name a value assigned inside the
        block) — is blocked on before the interval closes so async
        dispatch cannot leak out of the measurement.  The block runs
        inside a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``;
        after exit the context manager's ``span`` is the recorded
        :class:`Span`."""
        if axis not in AXES:
            raise ValueError(f"unknown axis {axis!r}; axes: {AXES}")
        return LiveSpan(self, name, axis, fence,
                        (stream, tick, rung, batch_size, track, shard))

    def instant(self, name: str, **tags) -> Span:
        """Zero-duration event (rung switch, admission decision, backend
        compile) on the shared timeline."""
        now = self.clock()
        return self.record(name, now, now, **tags)

    # ---------------- reading ----------------
    @property
    def n_recorded(self) -> int:
        """Total spans ever recorded (including overwritten ones)."""
        return self._n

    @property
    def dropped(self) -> int:
        """Spans lost to ring wrap-around."""
        return max(0, self._n - self.capacity)

    def spans(self) -> list[Span]:
        """Retained spans, oldest first (close order)."""
        with self._lock:
            n, cap = self._n, self.capacity
            if n <= cap:
                out = self._ring[:n]
            else:
                k = n % cap
                out = self._ring[k:] + self._ring[:k]
        return [s for s in out if s is not None]

    def clear(self) -> None:
        with self._lock:
            self._ring = [None] * self.capacity
            self._n = 0
            self._open.clear()
            self._counters.clear()

    # ---------------- counters ----------------
    def add(self, name: str, value: float) -> None:
        """Add ``value`` to the cumulative counter ``name``."""
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + value

    def counters(self) -> dict[str, float]:
        """A copy of every counter's running total."""
        with self._lock:
            return dict(self._counters)

    # ---------------- internals ----------------
    def _write(self, span: Span) -> None:
        """Ring-slot write; the caller holds the lock."""
        self._ring[self._n % self.capacity] = span
        self._n += 1


class LiveSpan:
    """One :meth:`SpanTracer.span` in flight: a profiler annotation around
    an interval on the tracer's clock.  ``span`` is the recorded
    :class:`Span` once the block has exited."""

    __slots__ = ("_tracer", "_note", "name", "axis", "fence", "tags",
                 "seq", "parent", "t0", "span", "sink")

    def __init__(self, tracer: SpanTracer, name: str, axis: str,
                 fence: Any, tags: tuple) -> None:
        self._tracer = tracer
        self._note = jax.profiler.TraceAnnotation(PROFILER_PREFIX + name)
        self.name = name
        self.axis = axis
        self.fence = fence
        self.tags = tags
        self.span: Optional[Span] = None
        # called with the recorded span once the block has exited
        self.sink: Optional[Callable[[Span], None]] = None

    def __enter__(self) -> "LiveSpan":
        tr = self._tracer
        self._note.__enter__()
        self.t0 = tr.clock()
        with tr._lock:
            self.seq = tr._next_seq
            tr._next_seq += 1
            outer = tr._open[-1] if tr._open else None
            self.parent = -1 if outer is None else outer.seq
            base = _TAG_DEFAULTS if outer is None else outer.tags
            self.tags = [b if v is None else v
                         for v, b in zip(self.tags, base)]
            tr._open.append(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        tr = self._tracer
        try:
            if self.fence is not None:
                fence = self.fence
                jax.block_until_ready(fence() if callable(fence) else fence)
        finally:
            t1 = tr.clock()
            self._note.__exit__(exc_type, exc, tb)
            stream, tick, rung, batch_size, track, shard = self.tags
            span = Span(self.name, self.t0, t1, stream, tick, rung,
                        batch_size, self.axis, track, self.parent, self.seq,
                        shard)
            with tr._lock:
                if tr._open and tr._open[-1] is self:
                    tr._open.pop()
                else:                  # out-of-order close
                    try:
                        tr._open.remove(self)
                    except ValueError:
                        pass
                tr._write(span)
            self.span = span
        if self.sink is not None:
            self.sink(span)
        return False
