"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

- busy: the union of the intervals in which an XLA operation ran on a
  device (``XLA Ops`` line of each ``/device:<KIND>:<n>`` plane), clipped to
  the traced window;
- idle share: 1 - busy / window, per device, and their mean;
- per-op device time, summed over devices, by operation name;
- per-module device time (``XLA Modules`` line), summed over devices, by
  module name with its ``(<id>)`` suffix removed, so every compiled copy of
  one jitted function counts under the function's name;
- idle gaps: every interval of the window in which a device ran nothing,
  labelled by the innermost host annotation (``jax.profiler.TraceAnnotation``
  whose name starts with ``bench.``) that covers the gap's midpoint, or
  ``host`` where none does, and summed by label.

The window is the host annotation ``bench.traced``; without one, the span
from the first to the last device operation.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(?!CPU)[A-Z_]+:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
PREFIX = "bench."
WINDOW = "bench.traced"
_SUFFIX = re.compile(r"\(\d+\)$")
_HLO = re.compile(r"^%?([\w.\-]+) = (\S+?)(?:\{[^}]*\})? ([\w\-]+)\(")
_HLO_TUPLE = re.compile(r"^%?([\w.\-]+) = \(.*?\) ([\w\-]+)\(")


@dataclasses.dataclass
class TraceSummary:
    devices: int
    window_s: float
    busy_s: float                  # mean over devices
    idle_pct: float                # mean over devices
    op_s: dict                     # op name -> seconds, summed over devices
    module_s: dict                 # module name -> seconds, summed over devices
    idle_gaps: dict                # host label -> idle seconds, mean over devices

    def top_ops(self, n: int = 10) -> list:
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]

    def top_gaps(self, n: int = 10) -> list:
        return sorted(self.idle_gaps.items(), key=lambda kv: -kv[1])[:n]


def short_op(name: str) -> str:
    """``%fusion.15 = bf16[8,48,160,16]{...} fusion(...)`` ->
    ``fusion.15 fusion bf16[8,48,160,16]``; other names unchanged."""
    m = _HLO.match(name)
    if m:
        return f"{m[1]} {m[3]} {m[2]}"
    m = _HLO_TUPLE.match(name)
    return f"{m[1]} {m[2]} tuple" if m else name


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``log_dir``."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def union(intervals: list) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _gaps(busy: list, lo: float, hi: float) -> list:
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


class _Labeller:
    """Innermost covering host annotation of a point in time."""

    def __init__(self, spans: list) -> None:
        # spans: (start, end, name); innermost = latest start that covers
        self.spans = sorted(spans)
        self.starts = [s for s, _, _ in self.spans]

    def __call__(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t)
        best = None
        # walk back over spans that started before t; the first one that
        # still covers t is the innermost (nested spans start later)
        for j in range(i - 1, max(i - 64, -1), -1):
            s, e, name = self.spans[j]
            if e >= t:
                best = name
                break
        return best or "host"


def reduce_profile(pd) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData``."""
    host_spans, window = [], None
    devices = []
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        span = (ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name)
                        if ev.name == WINDOW:
                            window = span[:2]
                        else:
                            host_spans.append(span)
        elif DEVICE_PLANE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(ev.name, ev.start_ns, ev.duration_ns)
                           for ev in line.events]
                elif line.name == MODULES_LINE:
                    mods = [(ev.name, ev.start_ns, ev.duration_ns)
                            for ev in line.events]
            devices.append((plane.name, ops, mods))
    devices = [d for d in devices if d[1]]
    if not devices:
        raise ValueError("the trace holds no device operation")
    if window is None:
        lo = min(s for _, ops, _ in devices for _, s, _ in ops)
        hi = max(s + d for _, ops, _ in devices for _, s, d in ops)
        window = (lo, hi)
    lo, hi = window
    span_s = (hi - lo) * 1e-9
    label = _Labeller(host_spans)
    op_s: dict = {}
    module_s: dict = {}
    gaps: dict = {}
    busy_total = 0.0
    for _, ops, mods in devices:
        busy = _clip(union([(s, s + d) for _, s, d in ops]), lo, hi)
        busy_total += sum(e - s for s, e in busy) * 1e-9
        for name, s, d in ops:
            if s + d > lo and s < hi:
                op_s[name] = op_s.get(name, 0.0) + d * 1e-9
        for name, s, d in mods:
            if s + d > lo and s < hi:
                key = _SUFFIX.sub("", name)
                module_s[key] = module_s.get(key, 0.0) + d * 1e-9
        for s, e in _gaps(busy, lo, hi):
            lab = label((s + e) / 2)
            gaps[lab] = gaps.get(lab, 0.0) + (e - s) * 1e-9 / len(devices)
    busy_s = busy_total / len(devices)
    return TraceSummary(
        devices=len(devices), window_s=span_s, busy_s=busy_s,
        idle_pct=100.0 * (1.0 - busy_s / span_s) if span_s > 0 else 0.0,
        op_s=op_s, module_s=module_s, idle_gaps=gaps)


def reduce_file(path: str) -> TraceSummary:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))
