"""tvcert CLI — static timing certification gate.

    python -m repro.analysis.cert --check            # CI gate (default)
    python -m repro.analysis.cert --regen            # retrace + remeasure
    python -m repro.analysis.cert --check --diff-out cert_diff.txt

``--check`` retraces the shipped tree (pure tracing, no XLA compile) and
compares against the committed ``analysis/certificate.json``; exit 1 on
any fatal finding (retrace violation, signature/envelope drift, new host
primitive, donation mismatch, roofline-vs-prior drift beyond ±25%).
``--regen`` rebuilds the static sections AND refreshes the measured
priors, then rewrites the certificate — review the diff
and commit it, golden-fixture style.  Exit codes: 0 clean, 1 findings,
2 usage/environment error (e.g. no committed certificate).
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.cert",
        description="jaxpr-level static timing certifier (tvcert)")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--check", action="store_true",
                      help="verify the committed certificate against the "
                           "shipped tree (default)")
    mode.add_argument("--regen", action="store_true",
                      help="retrace, remeasure priors, rewrite the "
                           "certificate")
    ap.add_argument("--cert", default=str(DEFAULT_CERT_PATH),
                    help="certificate path (default: %(default)s)")
    ap.add_argument("--tol", type=float, default=DRIFT_TOL,
                    help="relative drift tolerance for the prior/floor "
                         "ratio gate (default: %(default)s)")
    ap.add_argument("--diff-out", default=None, metavar="PATH",
                    help="also write the human-readable report to PATH")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress the report on stdout")
    args = ap.parse_args(argv)

    cert_path = Path(args.cert)

    if args.regen:
        cert = build_static()
        attach_measured(cert)
        problems = intrinsic_findings(cert)
        write_certificate(cert, cert_path)
        report = render_report(problems, [])
        if not args.quiet:
            sys.stdout.write(f"wrote {cert_path}\n" + report)
        if args.diff_out:
            Path(args.diff_out).write_text(report)
        return 1 if problems else 0

    if not cert_path.exists():
        sys.stderr.write(
            f"no certificate at {cert_path} — run --regen first\n")
        return 2
    committed = load_certificate(cert_path)
    fresh = build_static()
    fatal, notes = check(committed, fresh, tol=args.tol)
    report = render_report(fatal, notes)
    if not args.quiet:
        sys.stdout.write(report)
    if args.diff_out:
        Path(args.diff_out).write_text(report)
    return 1 if fatal else 0


if __name__ == "__main__":
    sys.exit(main())
