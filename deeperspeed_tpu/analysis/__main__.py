"""``python -m deeperspeed_tpu.analysis`` — the pre-merge static gate.

Runs both levels (AST repo-rule linter + compiled-program auditor),
applies ``ANALYSIS_SUPPRESSIONS.json``, prints findings, optionally
writes the findings JSON, and exits non-zero iff any *error*-level
finding survives suppression. ``scripts/check.sh`` runs this between
ruff and the strict trace validator.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


_REEXEC_MARK = "_DSTPU_ANALYSIS_REEXEC"


def _force_cpu_devices(n: int) -> None:
    """The program audit needs a multi-device host to see the SPMD
    programs; on CPU that means forcing virtual devices BEFORE jax
    initializes. Running ``python -m deeperspeed_tpu.analysis`` imports
    the parent package (and with it jax) before main() ever runs, so
    the only reliable way to apply the flags is to re-exec ourselves
    once with the environment set. No-op on real accelerators (audit
    those lowerings instead) and when the operator pre-set the flags."""
    if os.environ.get(_REEXEC_MARK) == "1":
        return
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if os.environ.get("JAX_PLATFORMS", "") != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}").strip()
    os.environ[_REEXEC_MARK] = "1"
    os.execv(sys.executable,
             [sys.executable, "-m", "deeperspeed_tpu.analysis"]
             + sys.argv[1:])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m deeperspeed_tpu.analysis",
        description="static auditor for jitted programs + repo-rule linter")
    p.add_argument("--root", default=None,
                   help="repo root (default: auto-detected from the "
                        "installed package location)")
    p.add_argument("--json", default=None, metavar="PATH",
                   help="write the findings report JSON here")
    p.add_argument("--write-baseline", action="store_true",
                   help="write <root>/ANALYSIS_BASELINE.json (the "
                        "committed snapshot of the post-suppression counts)")
    p.add_argument("--suppressions", default=None, metavar="PATH",
                   help="suppression file (default: "
                        "<root>/ANALYSIS_SUPPRESSIONS.json)")
    p.add_argument("--no-programs", action="store_true",
                   help="skip the compiled-program audit (level 1)")
    p.add_argument("--no-lint", action="store_true",
                   help="skip the AST repo-rule linter (level 2)")
    p.add_argument("--no-provenance", action="store_true",
                   help="skip the configs/ provenance check (level 3)")
    p.add_argument("--devices", type=int, default=8,
                   help="virtual CPU device count for the program audit")
    args = p.parse_args(argv)

    if not args.no_programs:
        _force_cpu_devices(args.devices)

    from .findings import (DEFAULT_BASELINE_FILE, DEFAULT_SUPPRESSIONS_FILE,
                           SuppressionError, apply_suppressions, format_text,
                           load_suppressions, report)

    root = args.root
    if root is None:
        here = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        root = here if os.path.isdir(os.path.join(here, "deeperspeed_tpu")) \
            else os.getcwd()

    findings = []
    notes = []
    if not args.no_lint:
        from .astlint import lint_paths
        findings.extend(lint_paths(root))
    if not args.no_programs:
        from .programs import audit_default_programs
        findings.extend(audit_default_programs(notes))
    if not args.no_provenance:
        from .provenance import check_config_provenance
        findings.extend(check_config_provenance(root))

    sup_path = args.suppressions or os.path.join(root,
                                                 DEFAULT_SUPPRESSIONS_FILE)
    try:
        sups = load_suppressions(sup_path)
    except SuppressionError as e:
        print(f"analysis: bad suppression file: {e}", file=sys.stderr)
        return 2
    kept, suppressed = apply_suppressions(findings, sups)
    for s in sups:
        if not s.used:
            notes.append(f"stale suppression never matched: "
                         f"{s.rule} @ {s.path} ({s.reason})")

    rep = report(kept, suppressed, root=root,
                 extra={"notes": notes} if notes else None)
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(rep, fh, indent=2, sort_keys=True)
    if args.write_baseline:
        with open(os.path.join(root, DEFAULT_BASELINE_FILE), "w") as fh:
            json.dump(rep, fh, indent=2, sort_keys=True)

    text = format_text(kept, suppressed)
    if text:
        print(text)
    for n in notes:
        print(f"note: {n}")
    c = rep["counts"]
    print(f"analysis: {c['error']} error(s), {c['warning']} warning(s), "
          f"{c['info']} info, {c['suppressed']} suppressed")
    return 1 if c["error"] else 0


if __name__ == "__main__":
    sys.exit(main())
