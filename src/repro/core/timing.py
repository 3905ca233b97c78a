"""Per-stage timeline instrumentation — the JAX analogue of the paper's
cProfiler breakdown (Fig. 3: read → pre-process → inference → post-process).

On an async dispatch runtime (XLA), naive ``time.time()`` around a jitted
call measures dispatch, not execution.  ``StageTimer`` fences with
``jax.block_until_ready`` on the stage outputs so the recorded interval is
the true device-inclusive stage latency, which is what the paper's
end-to-end numbers mean.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import defaultdict
from typing import Any, Callable, Iterator, Mapping, Sequence

import jax
import numpy as np

from .stats import LatencySummary, Welford, pearson, summarize

__all__ = [
    "StageRecord",
    "TimelineRecorder",
    "StageTimer",
    "timed_stage",
    "instrument",
    "STAGE_AXES",
]

# a stage timed with no tracer enters this in place of a span
_NO_SPAN = contextlib.nullcontext()

# Canonical stage names from the paper's Fig. 3 timeline.
READ = "read"
PRE = "pre_processing"
INFER = "inference"
POST = "post_processing"
CANONICAL_STAGES = (READ, PRE, INFER, POST)

# Default variation-axis tag per canonical stage (paper Table I): read is
# I/O-bound, pre/post scale with input content, inference is the model.
# Unknown stage names fall back to the residual end_to_end axis.
STAGE_AXES = {
    READ: "io",
    PRE: "data",
    INFER: "model",
    POST: "data",
    "upload": "io",
    "step": "model",
    "post": "data",
}


@dataclasses.dataclass
class StageRecord:
    """One job's timeline: stage → seconds, plus free-form scalar metadata
    (e.g. proposal counts — the paper correlates those with post time)."""

    stages: dict[str, float] = dataclasses.field(default_factory=dict)
    meta: dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def end_to_end(self) -> float:
        return sum(self.stages.values())


class TimelineRecorder:
    """Accumulates StageRecords across jobs and answers the paper's
    questions: per-stage summaries, variance attribution inputs, and
    correlation of any metadata series with end-to-end latency.

    When constructed with ``metrics=`` (a ``repro.obs.MetricsHub``,
    duck-typed so core stays obs-free), the recorder is a thin adapter:
    every added record is also forwarded to the hub keyed by this
    recorder's stream/rung tags plus the record's ``batch_size`` meta, so
    legacy recorders and the span tracer share one aggregation path."""

    def __init__(self, metrics: Any = None, stream: str = "",
                 rung: str = "") -> None:
        self.records: list[StageRecord] = []
        self._welford: dict[str, Welford] = defaultdict(Welford)
        self._metrics = metrics
        self._stream = stream
        self._rung = rung

    def add(self, record: StageRecord) -> None:
        self.records.append(record)
        for k, v in record.stages.items():
            self._welford[k].update(v)
        self._welford["end_to_end"].update(record.end_to_end)
        if self._metrics is not None:
            bs = int(record.meta.get("batch_size", 0))
            for k, v in record.stages.items():
                self._metrics.observe(self._stream, k, v,
                                      rung=self._rung, batch_size=bs)
            self._metrics.observe(self._stream, "end_to_end",
                                  record.end_to_end,
                                  rung=self._rung, batch_size=bs)

    def stage_series(self, stage: str) -> np.ndarray:
        return np.asarray([r.stages.get(stage, 0.0) for r in self.records])

    def meta_series(self, key: str) -> np.ndarray:
        return np.asarray([r.meta.get(key, 0.0) for r in self.records])

    def end_to_end_series(self) -> np.ndarray:
        return np.asarray([r.end_to_end for r in self.records])

    def stages(self) -> list[str]:
        keys: list[str] = []
        for r in self.records:
            for k in r.stages:
                if k not in keys:
                    keys.append(k)
        return keys

    def summary(self, stage: str | None = None) -> LatencySummary:
        if stage is None:
            return summarize(self.end_to_end_series())
        return summarize(self.stage_series(stage))

    def streaming(self, stage: str = "end_to_end") -> Welford:
        return self._welford[stage]

    def correlation_with_end_to_end(self, stage: str) -> float:
        """Table VI: corr(stage latency, end-to-end latency)."""
        return pearson(self.stage_series(stage), self.end_to_end_series())

    def correlation_meta(self, key: str, stage: str = POST) -> float:
        """Fig. 5: corr(#detected objects / proposals, post-processing)."""
        return pearson(self.meta_series(key), self.stage_series(stage))

    def breakdown_table(self) -> list[dict]:
        rows = []
        for st in self.stages():
            s = self.summary(st)
            rows.append(
                {
                    "stage": st,
                    "mean": s.mean,
                    "range": s.range,
                    "cv": s.cv,
                    "corr_e2e": self.correlation_with_end_to_end(st),
                }
            )
        return rows

    def dominant_stage(self) -> str:
        """The paper's inference-dominated vs post-processing-dominated
        classification: the stage whose latency correlates most with
        end-to-end latency (Table VI argmax)."""
        table = self.breakdown_table()
        if not table:
            raise ValueError("no records")
        return max(table, key=lambda r: r["corr_e2e"])["stage"]


class StageTimer:
    """Context-manager based per-job timer.

    Usage::

        rec = TimelineRecorder()
        timer = StageTimer()
        with timer.stage("read"):
            img = load()
        with timer.stage("inference"):
            out = jitted(img)           # fenced automatically
        timer.note("num_objects", n)
        rec.add(timer.finish())

    With ``tracer=`` (anything with the ``span`` context manager of
    ``repro.obs.SpanTracer`` or ``repro.obs.Observatory``, duck-typed)
    every stage runs inside a live span carrying ``tags``
    (stream/tick/rung/batch_size/track) and the stage's default axis, so
    spans the stage's body opens nest under it — there is exactly one
    recording path.  The record's sums come from this timer's own clock
    either way."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 tracer: Any = None,
                 tags: Mapping[str, Any] | None = None) -> None:
        self._clock = clock
        self._record = StageRecord()
        self._tracer = tracer
        self._tags = dict(tags or {})

    @contextlib.contextmanager
    def stage(self, name: str) -> Iterator[None]:
        with (_NO_SPAN if self._tracer is None else self._tracer.span(
                name, axis=STAGE_AXES.get(name, "end_to_end"), **self._tags)):
            t0 = self._clock()
            try:
                yield
            finally:
                t1 = self._clock()
                self._record.stages[name] = (
                    self._record.stages.get(name, 0.0) + t1 - t0
                )

    def note(self, key: str, value: float) -> None:
        self._record.meta[key] = float(value)

    def finish(self) -> StageRecord:
        rec, self._record = self._record, StageRecord()
        return rec


@contextlib.contextmanager
def timed_stage(timer: StageTimer, name: str, *fence: Any) -> Iterator[None]:
    """Like ``timer.stage`` but fences on device values before closing the
    interval so async dispatch does not leak into the next stage."""
    with timer.stage(name):
        yield
        if fence:
            jax.block_until_ready(fence)


def instrument(
    fn: Callable[..., Any], name: str, timer: StageTimer
) -> Callable[..., Any]:
    """Wrap ``fn`` so every call is recorded as stage ``name`` with a
    block_until_ready fence on its outputs."""

    def wrapped(*args, **kwargs):
        t_ctx = timer.stage(name)
        with t_ctx:
            out = fn(*args, **kwargs)
            jax.block_until_ready(out)
        return out

    wrapped.__name__ = f"timed_{name}"
    return wrapped


def run_pipeline(
    stages: Sequence[tuple[str, Callable[[Any], Any]]],
    inputs: Iterator[Any],
    recorder: TimelineRecorder,
    meta_fn: Callable[[Any], Mapping[str, float]] | None = None,
    warmup: int = 1,
) -> list[Any]:
    """Drive a (name, fn) pipeline over an input stream recording the full
    per-stage timeline of every job — the paper's profiling harness.

    ``warmup`` jobs are executed but not recorded (XLA compilation on the
    first call would otherwise appear as a giant outlier; the paper similarly
    discards cold-start frames).
    """
    outputs: list[Any] = []
    for i, item in enumerate(inputs):
        timer = StageTimer()
        value = item
        for name, fn in stages:
            with timer.stage(name):
                value = fn(value)
                jax.block_until_ready(value)
        if meta_fn is not None:
            for k, v in meta_fn(value).items():
                timer.note(k, v)
        rec = timer.finish()
        if i >= warmup:
            recorder.add(rec)
        outputs.append(value)
    return outputs
