"""tvcert — jaxpr-level static timing certifier.

Static companion to the runtime ``TraceSentinel`` and the AST-level
``tvlint``: instead of watching a live engine or pattern-matching
source, it traces every registered hot-path program to a closed jaxpr
over the declared input envelope and certifies, before any frame runs:

* **retrace-freedom** — every envelope point (rung × batch-size ×
  occupancy, plus join/leave/carve-out churn) maps to an already-seen
  aval signature;
* **cost honesty** — static FLOP/byte counts yield a roofline latency
  floor per (rung, batch-size), cross-checked against the learned
  cost-model priors (floor ≤ prior, and the drift gate);
* **host hygiene** — no host-interaction primitive (callbacks, infeed,
  stray ``device_put``) hides inside a compiled program, and declared
  buffer donation matches what the traced program actually carries.

The committed ``analysis/certificate.json`` pins all of it; the
``python -m repro.analysis.cert --check`` gate recomputes the static
parts and fails CI on drift.  See ``envelope`` (the input universe),
``tracer`` (recorder-instrumented engine sweeps), ``costs`` (primitive
counting), ``roofline`` (floors + drift gate), ``certificate``
(assembly/serialization/check).
"""
from .certificate import (
    DEFAULT_CERT_PATH,
    DRIFT_TOL,
    attach_measured,
    build_static,
    check,
    intrinsic_findings,
    load_certificate,
    render_report,
    write_certificate,
)
from .costs import Counts, count_jaxpr, outer_donated_invars, program_io_bytes
from .envelope import (
    DTYPES,
    InputEnvelope,
    KernelPoint,
    RungPoint,
    default_envelope,
    envelope_hash,
)
from .roofline import CPU_2CORE, Hardware, drift_findings, roofline_floor
from .tracer import (
    ProgramRecorder,
    ProgramSummary,
    RungTrace,
    aval_signature,
    certify_rung,
    trace_kernel,
    trace_ladder_rung,
)

__all__ = [
    "DEFAULT_CERT_PATH", "DRIFT_TOL", "attach_measured", "build_static",
    "check", "intrinsic_findings", "load_certificate", "render_report",
    "write_certificate",
    "Counts", "count_jaxpr", "outer_donated_invars", "program_io_bytes",
    "DTYPES", "InputEnvelope", "KernelPoint", "RungPoint",
    "default_envelope", "envelope_hash",
    "CPU_2CORE", "Hardware", "drift_findings", "roofline_floor",
    "ProgramRecorder", "ProgramSummary", "RungTrace", "aval_signature",
    "certify_rung", "trace_kernel", "trace_ladder_rung",
]
