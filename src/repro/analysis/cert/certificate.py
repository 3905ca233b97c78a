"""Building, serializing, and checking the timing certificate.

The committed artifact (``analysis/certificate.json``) has two kinds of
content, split by how they are produced:

* **static** — envelope hash, per-program aval signatures, FLOP/byte
  counts, host-primitive scan, donation cross-check, roofline floors.
  Recomputed *exactly* at ``--check`` time from the shipped code by pure
  tracing (no XLA compile, no frame executes); any difference against
  the committed values is a finding.
* **measured** — per-(rung, batch-size) cold-start cost-model priors
  (``prior_s``, from a short calibration run).  Only refreshed at
  ``--regen``, committed like golden fixtures; ``--check`` treats them
  as constants and re-derives just the *ratios* against the fresh
  floors.

Severity follows the retrace-hazard model: signature drift, sweep
violations, new host primitives, and donation mismatches are **fatal**
(the envelope claim no longer holds); FLOP/byte count changes alone are
**notes** — magnitude drift is what the prior/floor ratio gate (±25%)
exists to catch.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from .costs import count_jaxpr, program_io_bytes
from .envelope import InputEnvelope, default_envelope, envelope_hash
from .roofline import CPU_2CORE, Hardware, roofline_floor
from .tracer import certify_rung, trace_kernel, trace_ladder_rung

__all__ = [
    "CERT_VERSION",
    "DEFAULT_CERT_PATH",
    "DRIFT_TOL",
    "build_static",
    "attach_measured",
    "check",
    "intrinsic_findings",
    "render_report",
    "load_certificate",
    "write_certificate",
]

CERT_VERSION = 1
DEFAULT_CERT_PATH = Path("analysis") / "certificate.json"
DRIFT_TOL = 0.25


def _cost_row(point, batch: int, env: InputEnvelope, hw: Hardware) -> dict:
    """Static roofline row for one (rung, batch-size): the vmapped fused
    step at batch ``batch`` — the exact program an engine with
    ``capacity == batch`` runs."""
    from repro.perception.pipelines import build_pipeline, preprocess_device

    built = build_pipeline(point.pipeline, scale=point.scale, pad=point.pad)
    step = jax.vmap(
        lambda raw: built.infer(preprocess_device(raw, built.scale, built.pad)))
    spec = jax.ShapeDtypeStruct((batch, *env.image_shape), jnp.float32)
    closed = jax.make_jaxpr(step)(spec)
    counts = count_jaxpr(closed)
    in_b, out_b = program_io_bytes(closed)
    bytes_min = in_b + out_b
    # steady state: every slot dirty every tick → h2d = whole batch
    h2d = float(batch * int(np.prod(env.image_shape)) * 4)
    floor = roofline_floor(counts.flops, bytes_min, h2d, hw)
    return {
        "rung": point.name,
        "batch_size": int(batch),
        "flops": counts.flops,
        "bytes_min": bytes_min,
        "h2d_bytes": h2d,
        "intensity": counts.flops / bytes_min if bytes_min else 0.0,
        "floor_s": floor,
        "prior_s": None,
        "ratio": None,
    }


def build_static(env: InputEnvelope | None = None,
                 hw: Hardware = CPU_2CORE,
                 engine_cls=None) -> dict:
    """Trace the whole envelope and assemble the static certificate.

    Pure tracing end to end — zero XLA compiles, zero inference FLOPs.
    ``engine_cls`` substitutes the batched engine class (the injection
    acceptance test passes a mutated copy).
    """
    if env is None:
        env = default_envelope()

    programs: dict[str, dict] = {}
    violations: list[list] = []
    for point in env.rungs:
        trace = certify_rung(point, env, engine_cls=engine_cls)
        for name, summary in trace.programs.items():
            programs[name] = summary.to_dict()
        violations.extend([list(v) for v in trace.violations])
    for point in env.ladder_rungs:
        summary = trace_ladder_rung(point, env)
        programs[summary.name] = summary.to_dict()
    for kp in env.kernels:
        summary = trace_kernel(kp)
        programs[summary.name] = summary.to_dict()

    # tvlint: disable=TV002,TV005 (analysis-time tracing: _cost_row only
    # builds jaxprs via make_jaxpr — nothing compiles or executes)
    cost_table = [_cost_row(point, b, env, hw)
                  for point in env.rungs for b in env.batch_sizes]

    # fleet sharding: at data=K every slot-batch program runs as one
    # SPMD executable over K devices.  jit signatures key on *global*
    # avals, so the per-program signatures above certify every declared
    # K unchanged; the K-specific claim is the slot-block partition —
    # capacity must divide so each shard owns an equal contiguous block
    # (slot_batch_spec raises otherwise), checked here statically.
    fleet = []
    for k in env.fleet_shards:
        divides = env.capacity % k == 0
        fleet.append({
            "data_shards": int(k),
            "slot_spec": "data" if k > 1 else None,
            "slots_per_shard": env.capacity // k if divides else None,
        })
        if not divides:
            violations.append([
                "fleet/slot_batch_spec",
                f"capacity {env.capacity} not divisible by data axis {k}",
                f"data={k}"])

    return {
        "version": CERT_VERSION,
        "envelope_hash": envelope_hash(env),
        "envelope": env.describe(),
        "hardware": hw.to_dict(),
        "programs": programs,
        "violations": violations,
        "cost_table": cost_table,
        "fleet": fleet,
    }


def attach_measured(cert: dict, env: InputEnvelope | None = None,
                    calib_n: int = 4) -> dict:
    """Fill the measured columns at ``--regen`` time.

    * ``prior_s`` — the cold-start (rung, batch-size) cost-model prior
      from a short calibration (``anytime.calibrate`` at ``calib_n``
      frames per rung), via ``cold_start_prior_table``;
    * ``ratio`` — ``prior_s / floor_s``, the drift-gate anchor.
    """
    from repro.anytime.cost import cold_start_prior_table
    from repro.anytime.ladder import Rung, calibrate
    from repro.perception.data import SceneConfig

    if env is None:
        env = default_envelope()
    rungs = [Rung(p.name, p.pipeline, p.scale) for p in env.rungs]
    ladder = calibrate(rungs, SceneConfig(), n=calib_n)
    priors = cold_start_prior_table(list(ladder), env.batch_sizes)

    for row in cert["cost_table"]:
        key = (row["rung"], row["batch_size"])
        if key in priors:
            row["prior_s"] = priors[key]
            row["ratio"] = (priors[key] / row["floor_s"]
                            if row["floor_s"] > 0 else None)
    return cert


# ---------------------------------------------------------------------------
# checking
# ---------------------------------------------------------------------------

def intrinsic_findings(static: dict) -> list[str]:
    """Fatal problems a static build carries on its own, before any
    comparison against a committed certificate."""
    findings = []
    for prog, sig, where in static.get("violations", []):
        findings.append(
            f"RETRACE {prog}: new aval signature {sig} after freeze "
            f"(envelope point: {where})")
    for name, p in sorted(static.get("programs", {}).items()):
        declared = set(p.get("declared_donation", []))
        traced = p.get("donated_invars")
        if traced is not None:
            actual = {i for i, d in enumerate(traced) if d}
            if declared != actual:
                findings.append(
                    f"DONATION {name}: declared donate_argnums "
                    f"{sorted(declared)} but traced program donates "
                    f"{sorted(actual)}")
        elif declared:
            findings.append(
                f"DONATION {name}: declares donate_argnums "
                f"{sorted(declared)} but the traced program carries no "
                "donation metadata")
    return findings


def check(committed: dict, fresh: dict, tol: float = DRIFT_TOL
          ) -> tuple[list[str], list[str]]:
    """Compare a committed certificate against a freshly traced static
    build.  Returns ``(fatal, notes)``: fatal findings fail the gate,
    notes are informational drift."""
    fatal = list(intrinsic_findings(fresh))
    notes: list[str] = []

    if committed.get("version") != fresh["version"]:
        fatal.append(
            f"VERSION certificate v{committed.get('version')} != "
            f"checker v{fresh['version']} — regenerate")
        return fatal, notes
    if committed.get("envelope_hash") != fresh["envelope_hash"]:
        fatal.append(
            f"ENVELOPE hash {committed.get('envelope_hash')} → "
            f"{fresh['envelope_hash']}: the declared input set changed "
            "(rung, batch size, shape, or kernel aval) — review and "
            "--regen")
    if committed.get("hardware") != fresh["hardware"]:
        fatal.append(
            "HARDWARE model changed "
            f"({committed.get('hardware', {}).get('name')} → "
            f"{fresh['hardware']['name']}) — review and --regen")
    if committed.get("fleet") != fresh.get("fleet"):
        fatal.append(
            "FLEET slot-block partition changed "
            f"({committed.get('fleet')} → {fresh.get('fleet')}) — the "
            "sharded serving layout is part of the envelope claim; "
            "review and --regen")

    old_p = committed.get("programs", {})
    new_p = fresh["programs"]
    for name in sorted(set(old_p) - set(new_p)):
        fatal.append(f"PROGRAM {name} disappeared from the traced set")
    for name in sorted(set(new_p) - set(old_p)):
        fatal.append(f"PROGRAM {name} is new (uncertified) — --regen")
    for name in sorted(set(old_p) & set(new_p)):
        o, n = old_p[name], new_p[name]
        if o["signatures"] != n["signatures"]:
            fatal.append(
                f"SIGNATURES {name}: {o['signatures']} → "
                f"{n['signatures']} — traced aval set changed")
        new_hosts = set(map(tuple, n.get("host_prims", []))) \
            - set(map(tuple, o.get("host_prims", [])))
        for path, prim in sorted(new_hosts):
            fatal.append(
                f"HOSTPRIM {name}: new host-interaction primitive "
                f"{prim} at {path} inside the compiled program")
        for field in ("flops", "mem_bytes", "transcendentals"):
            if o.get(field) != n.get(field):
                notes.append(
                    f"{name}: {field} {o.get(field)} → {n.get(field)}")
        if o.get("unknown") != n.get("unknown"):
            notes.append(
                f"{name}: uncounted primitives {o.get('unknown')} → "
                f"{n.get('unknown')}")

    old_rows = {(r["rung"], r["batch_size"]): r
                for r in committed.get("cost_table", [])}
    for row in fresh["cost_table"]:
        key = (row["rung"], row["batch_size"])
        label = f"{key[0]}/batch{key[1]}"
        old = old_rows.get(key)
        if old is None:
            fatal.append(f"COST {label}: no committed row — --regen")
            continue
        floor = row["floor_s"]
        prior, ratio = old.get("prior_s"), old.get("ratio")
        if prior is not None and floor > prior:
            fatal.append(
                f"FLOOR {label}: static floor {floor * 1e3:.2f}ms exceeds "
                f"the cost-model prior {prior * 1e3:.2f}ms — counts or "
                "hardware model are wrong, or the model got cheaper "
                "without recalibration")
        if prior is not None and ratio is not None and ratio > 0:
            live = prior / floor if floor > 0 else float("inf")
            drift = abs(live - ratio) / ratio
            if drift > tol:
                fatal.append(
                    f"DRIFT {label}: prior/floor ratio moved {drift:.0%} "
                    f"(committed {ratio:.1f}, recomputed {live:.1f}, tol "
                    f"{tol:.0%}) — static cost and learned prior have "
                    "diverged; recalibrate or --regen")
    return fatal, notes


def render_report(fatal: list[str], notes: list[str]) -> str:
    """Human-readable gate report (written as the CI diff artifact)."""
    lines = ["tvcert check: " + ("FAIL" if fatal else "PASS"), ""]
    if fatal:
        lines.append(f"{len(fatal)} fatal finding(s):")
        lines += [f"  [FATAL] {f}" for f in fatal]
        lines.append("")
    if notes:
        lines.append(f"{len(notes)} note(s):")
        lines += [f"  [note]  {n}" for n in notes]
        lines.append("")
    if not fatal and not notes:
        lines.append("certificate matches the shipped tree exactly.")
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# serialization
# ---------------------------------------------------------------------------

def load_certificate(path: str | Path) -> dict:
    return json.loads(Path(path).read_text())


def write_certificate(cert: dict, path: str | Path) -> None:
    p = Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(cert, indent=2, sort_keys=True) + "\n")
