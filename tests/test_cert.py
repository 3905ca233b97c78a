"""Tests for tvcert — the jaxpr-level static timing certifier.

Covers: closed-form FLOP/byte counting, host-primitive and donation
detection, the retrace-freedom sweep (shipped tree certifies clean; an
injected shape-dependent branch flips the gate), the roofline-vs-prior
drift gate, the floor-below-prior invariant, and the CLI exit
codes."""
import json
import shutil
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.cert import (
    CPU_2CORE,
    InputEnvelope,
    RungPoint,
    aval_signature,
    build_static,
    certify_rung,
    check,
    count_jaxpr,
    default_envelope,
    drift_findings,
    envelope_hash,
    intrinsic_findings,
    outer_donated_invars,
    program_io_bytes,
    roofline_floor,
)
from repro.analysis.cert.__main__ import main as cert_main
from repro.perception.data import H, W

REPO = Path(__file__).parent.parent
CERT_PATH = REPO / "analysis" / "certificate.json"


def _small_env(**kw) -> InputEnvelope:
    """A fast envelope: one rung, capacity 2, no ladder/kernels."""
    defaults = dict(
        capacity=2,
        occupancies=(1, 2),
        batch_sizes=(1,),
        image_shape=(H, W, 3),
        rungs=(RungPoint("early_exit", "early_exit"),),
        ladder_rungs=(),
        kernels=(),
        churn=True,
    )
    defaults.update(kw)
    return InputEnvelope(**defaults)


# ------------------------------------------------------ counting ------

def test_dot_general_closed_form():
    m, k, n = 7, 13, 5
    f = lambda a, b: a @ b
    closed = jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((m, k), jnp.float32),
        jax.ShapeDtypeStruct((k, n), jnp.float32))
    counts = count_jaxpr(closed)
    assert counts.flops == 2 * m * k * n


def test_conv_closed_form():
    n, h, w, cin, cout, kh, kw = 1, 8, 8, 3, 4, 3, 3
    def f(x, kern):
        return jax.lax.conv_general_dilated(
            x, kern, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    closed = jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((n, h, w, cin), jnp.float32),
        jax.ShapeDtypeStruct((kh, kw, cin, cout), jnp.float32))
    counts = count_jaxpr(closed)
    assert counts.flops == 2 * (n * h * w * cout) * cin * kh * kw


def test_reduce_and_transcendental_counts():
    n = 64
    f = lambda x: jnp.sum(jnp.exp(x))
    closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((n,), jnp.float32))
    counts = count_jaxpr(closed)
    assert counts.transcendentals == n           # exp: one per element
    assert counts.by_prim.get("reduce_sum") == n  # sum: one per input elt


def test_scan_scales_body_by_length():
    L = 11
    def f(x):
        def body(c, _):
            return c * 2.0, None
        out, _ = jax.lax.scan(body, x, None, length=L)
        return out
    closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((4,), jnp.float32))
    counts = count_jaxpr(closed)
    assert counts.by_prim.get("mul") == 4 * L


def test_program_io_bytes():
    f = lambda a, b: a + b
    closed = jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((10,), jnp.float32),
        jax.ShapeDtypeStruct((10,), jnp.float32))
    in_b, out_b = program_io_bytes(closed)
    assert in_b == 80.0 and out_b == 40.0


def test_host_primitive_detected_inside_jitted_program():
    def f(x):
        y = jax.pure_callback(
            lambda a: np.asarray(a),
            jax.ShapeDtypeStruct((4,), np.float32), x)
        return y + 1.0
    closed = jax.make_jaxpr(jax.jit(f))(
        jax.ShapeDtypeStruct((4,), jnp.float32))
    counts = count_jaxpr(closed)
    assert counts.host_prims, "pure_callback must be reported"
    assert any("callback" in p for p in counts.host_prims)


def test_donation_visible_in_traced_jaxpr():
    f = jax.jit(lambda buf, x: buf + x, donate_argnums=(0,))
    closed = jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((4,), jnp.float32),
        jax.ShapeDtypeStruct((4,), jnp.float32))
    assert outer_donated_invars(closed) == (True, False)


def test_intrinsic_findings_flag_donation_mismatch():
    static = {
        "violations": [],
        "programs": {
            "rung/slot_update": {
                "declared_donation": [0],
                "donated_invars": [False, False, False],
            },
        },
    }
    findings = intrinsic_findings(static)
    assert findings and "DONATION" in findings[0]


def test_roofline_floor_is_max_of_terms():
    hw = CPU_2CORE
    assert roofline_floor(hw.peak_flops, 0, 0, hw) == 1.0
    assert roofline_floor(0, hw.mem_bw * 2, 0, hw) == 2.0
    assert roofline_floor(0, 0, hw.h2d_bw * 3, hw) == 3.0


def test_aval_signature_format():
    sig = aval_signature((jnp.zeros((2, 3), jnp.float32),
                          jnp.zeros((), jnp.int32)))
    assert sig == "(f32[2,3], i32[])"


# ----------------------------------------------- retrace-freedom ------

def test_small_envelope_certifies_retrace_free():
    env = _small_env()
    trace = certify_rung(env.rungs[0], env)
    assert trace.violations == []
    step = trace.programs["early_exit/step"]
    assert len(step["signatures"] if isinstance(step, dict)
               else step.signatures) == 1


def test_injected_shape_dependent_branch_flips_the_gate(tmp_path):
    """The acceptance test: copy batched/{engine,executor}.py, inject a
    branch that steps a *sliced* batch when occupancy < capacity, and
    certify — the sweep must report a retrace violation that fails the
    gate, where the unmodified engine certifies clean."""
    pkg = tmp_path / "mutated_batched"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    shutil.copy(REPO / "src" / "repro" / "batched" / "executor.py",
                pkg / "executor.py")
    src = (REPO / "src" / "repro" / "batched" / "engine.py").read_text()
    needle = "self._exec.submit(slot_frames, payload=None)"
    assert needle in src
    inject = ("if len(slot_frames) < self.capacity:\n"
              "                self._exec._step(self._exec._raw"
              "[: max(len(slot_frames), 1)])\n"
              "            " + needle)
    (pkg / "engine.py").write_text(src.replace(needle, inject))

    sys.path.insert(0, str(tmp_path))
    try:
        import mutated_batched.engine as meng
        env = _small_env()
        trace = certify_rung(env.rungs[0], env,
                             engine_cls=meng.BatchedPerceptionEngine)
    finally:
        sys.path.remove(str(tmp_path))
        sys.modules.pop("mutated_batched.engine", None)
        sys.modules.pop("mutated_batched", None)

    assert trace.violations, "the sliced-batch step must retrace"
    progs, sigs, contexts = zip(*trace.violations)
    assert any("step" in p for p in progs)
    static = {"violations": [list(v) for v in trace.violations],
              "programs": {}}
    findings = intrinsic_findings(static)
    assert findings and "RETRACE" in findings[0]


# ----------------------------------------------------- drift gate -----

@pytest.fixture(scope="module")
def shipped_cert():
    assert CERT_PATH.exists(), "commit analysis/certificate.json (--regen)"
    return json.loads(CERT_PATH.read_text())


def test_drift_gate_fires_on_2x_prior_perturbation(shipped_cert):
    rows = [r for r in shipped_cert["cost_table"]
            if r.get("ratio") is not None]
    assert rows, "certificate must carry measured priors"
    honest = {(r["rung"], r["batch_size"]): r["prior_s"] for r in rows}
    perturbed = {k: 2.0 * v for k, v in honest.items()}
    assert drift_findings(rows, honest) == []
    findings = drift_findings(rows, perturbed)
    assert len(findings) == len(rows), \
        "a 2x prior shift must trip every row at 25% tolerance"


def test_static_floor_below_every_measured_p50(shipped_cert):
    rows = shipped_cert["cost_table"]
    measured = [r for r in rows if r.get("prior_s") is not None]
    assert len(measured) == 12, \
        "every (rung, batch-size) needs a calibrated cost-model prior"
    for r in measured:
        assert r["floor_s"] <= r["prior_s"], \
            f"{r['rung']}/b{r['batch_size']}: floor above cost-model prior"


def test_certificate_has_no_benchmark_column(shipped_cert):
    """The certificate's measured columns come from the cost model alone:
    no row carries a host-clock benchmark timing, and a fresh static row
    has exactly the committed row's keys."""
    rows = shipped_cert["cost_table"]
    assert rows
    for r in rows:
        assert "bench_p50_s" not in r, f"{r['rung']}/b{r['batch_size']}"
    env = _small_env()
    fresh = build_static(env)["cost_table"][0]
    assert set(fresh) == set(rows[0])


# ------------------------------------------------------- CLI gate -----

def test_shipped_tree_certifies_clean(regen_cert, tmp_path):
    """The CI gate: the committed certificate matches a fresh static
    trace of the shipped tree.  ``--regen-cert``/``--regen-fixtures``
    rewrites it instead."""
    import os
    cwd = os.getcwd()
    os.chdir(REPO)
    try:
        if regen_cert:
            assert cert_main(["--regen", "--cert", str(CERT_PATH),
                              "--quiet"]) == 0
        report = tmp_path / "report.txt"
        rc = cert_main(["--check", "--cert", str(CERT_PATH),
                        "--diff-out", str(report), "--quiet"])
        assert rc == 0, report.read_text()
        assert "PASS" in report.read_text()
    finally:
        os.chdir(cwd)


def test_cli_missing_certificate_is_usage_error(tmp_path):
    assert cert_main(["--check",
                      "--cert", str(tmp_path / "nope.json")]) == 2


def test_cli_envelope_regression_fails_gate(shipped_cert, tmp_path):
    stale = dict(shipped_cert)
    stale["envelope_hash"] = "0" * 16
    p = tmp_path / "stale.json"
    p.write_text(json.dumps(stale))
    report = tmp_path / "report.txt"
    rc = cert_main(["--check", "--cert", str(p),
                    "--diff-out", str(report), "--quiet"])
    assert rc == 1
    assert "ENVELOPE" in report.read_text()


def test_check_reports_signature_drift_as_fatal(shipped_cert):
    fresh = json.loads(json.dumps(shipped_cert))   # deep copy
    name = sorted(fresh["programs"])[0]
    fresh["programs"][name]["signatures"] = ["(f32[1,1,1,1])"]
    fatal, _notes = check(shipped_cert, fresh)
    assert any("SIGNATURES" in f for f in fatal)


def test_check_reports_count_drift_as_note_only(shipped_cert):
    fresh = json.loads(json.dumps(shipped_cert))
    name = sorted(fresh["programs"])[0]
    fresh["programs"][name]["flops"] = \
        fresh["programs"][name]["flops"] + 1.0
    fatal, notes = check(shipped_cert, fresh)
    assert not fatal
    assert any("flops" in n for n in notes)


def test_envelope_hash_pins_the_input_set():
    a = _small_env()
    b = _small_env(batch_sizes=(1, 2))
    assert envelope_hash(a) != envelope_hash(b)
    assert envelope_hash(a) == envelope_hash(_small_env())
