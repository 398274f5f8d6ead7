"""``d9d-audit`` console entry (also ``python -m tools.audit``).

Default mode traces the registered hot executables at tiny config on
the local backend (tools/audit/harness.py) with artifact capture on,
then checks every captured fact against the committed
``AUDIT_BASELINE.json`` (expectations + accepted-violation baseline) —
the same committed-baseline gate shape as ``d9d-lint`` and
``tools/bench_compare.py``: exit nonzero on NEW violations (or on an
expectation that matched nothing — a contract that silently stopped
being checked), stale baseline entries reported so the file shrinks as
debt is paid.

``--facts`` audits an existing telemetry JSONL capture instead of
running the harness — the flow for a chip run made with
``D9D_AUDIT_CAPTURE=1`` in its environment, so that the ``executable``
events carry ``audit`` blocks.
"""

import argparse
import json
import os
import pathlib
import sys

# the harness needs a multi-device CPU mesh for the ZeRO / pp legs;
# must be set before jax initializes its backends (conftest does the
# same for the in-process tier-1 gate)
if "xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from tools.audit import manifest as manifest_mod  # noqa: E402
from tools.audit.rules import RULE_SUMMARIES, run_rules  # noqa: E402

REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]
DEFAULT_BASELINE = REPO_ROOT / "AUDIT_BASELINE.json"


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="d9d-audit",
        description=(
            "static analyzer over compiled artifacts: collective "
            "schedules, donation coverage, baked constants, dtype "
            "discipline, host callbacks "
            "(docs/design/static_analysis.md)"
        ),
    )
    parser.add_argument(
        "--facts", nargs="*", default=None, metavar="JSONL",
        help="audit executable events from telemetry JSONL captures "
             "instead of running the trace harness (TPU bench legs)",
    )
    parser.add_argument(
        "--legs", default=None,
        help="comma-separated harness legs to run (default: all; "
             "--list-legs to see them)",
    )
    parser.add_argument(
        "--baseline", default=None,
        help=f"manifest file (default: {DEFAULT_BASELINE.name} at the "
             "repo root)",
    )
    parser.add_argument(
        "--write-baseline", action="store_true",
        help="rewrite the manifest's baseline section from the current "
             "violations (expectations kept; NEW entries get a FILL-ME "
             "reason the loader rejects until a human justifies them)",
    )
    parser.add_argument("--json", action="store_true", dest="as_json")
    parser.add_argument(
        "--list-rules", action="store_true",
        help="print the rule set and exit",
    )
    parser.add_argument(
        "--list-legs", action="store_true",
        help="print the harness legs and exit",
    )
    return parser


def facts_from_jsonl(paths: list[str]) -> list[dict]:
    """``audit`` blocks of ``executable`` events in telemetry JSONL
    files (lenient line-by-line parse: a crashed process's truncated
    log must still audit)."""
    facts = []
    for path in paths:
        with open(path, encoding="utf-8") as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    ev = json.loads(line)
                except ValueError:
                    continue
                if ev.get("kind") == "executable" and "audit" in ev:
                    facts.append(ev["audit"])
    return facts


def _violation_dict(v) -> dict:
    return {
        "rule": v.rule,
        "context": v.context,
        "executable": v.executable,
        "message": v.message,
        "fingerprint": v.fingerprint(),
    }


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.list_rules:
        for rule_id in sorted(RULE_SUMMARIES):
            print(f"{rule_id} {RULE_SUMMARIES[rule_id]}")
        return 0
    if args.list_legs:
        from tools.audit.harness import LEGS

        for name in LEGS:
            print(name)
        return 0

    baseline_path = (
        pathlib.Path(args.baseline) if args.baseline else DEFAULT_BASELINE
    )
    if args.write_baseline and (args.legs or args.facts is not None):
        # a partial capture must never rewrite the committed baseline:
        # write_baseline rebuilds the section from THIS run's
        # violations, so entries (and their hand-written reasons) for
        # every un-run context would be silently erased — the same
        # refusal d9d-lint makes for --select/partial scans
        print(
            "d9d-audit: --write-baseline refuses to run with --legs or "
            "--facts (a partial capture would erase the other "
            "contexts' baseline entries and their reasons); run the "
            "full harness", file=sys.stderr,
        )
        return 2
    try:
        manifest = manifest_mod.load(baseline_path)
    except manifest_mod.AuditManifestError as e:
        print(f"d9d-audit: {e}", file=sys.stderr)
        return 2

    if args.facts is not None:
        if not args.facts:
            print(
                "d9d-audit: --facts needs at least one telemetry JSONL "
                "file", file=sys.stderr,
            )
            return 2
        facts = facts_from_jsonl(args.facts)
    else:
        from tools.audit.harness import trace_registered_executables

        legs = (
            [s.strip() for s in args.legs.split(",") if s.strip()]
            if args.legs
            else None
        )
        try:
            facts = trace_registered_executables(legs)
        except (RuntimeError, ValueError) as e:
            print(f"d9d-audit: {e}", file=sys.stderr)
            return 2

    if not facts:
        print(
            "d9d-audit: no audit facts captured — nothing to certify "
            "(for --facts inputs, the producing run must export "
            "D9D_AUDIT_CAPTURE=1)", file=sys.stderr,
        )
        return 2

    report = run_rules(facts, manifest)
    diff = manifest_mod.diff_against_baseline(
        report.violations, manifest
    )
    # a FULL harness run leaves no excuse for an expectation context
    # with zero facts: every leg ran, so a missing context means a
    # renamed/dropped leg silently retiring its whole contract table —
    # fail like an unmatched expectation. Partial runs (--legs,
    # --facts captures) legitimately cover a subset: notes only.
    full_run = args.facts is None and not args.legs

    if args.write_baseline:
        data = manifest_mod.write_baseline(
            baseline_path, report.violations, previous=manifest
        )
        fill_me = sum(
            1 for e in data["baseline"]
            if str(e["reason"]).startswith("FILL-ME")
        )
        print(
            f"d9d-audit: wrote {len(data['baseline'])} baseline "
            f"entr{'y' if len(data['baseline']) == 1 else 'ies'} to "
            f"{baseline_path}"
            + (
                f" — {fill_me} need a reason before the gate will "
                "load the file" if fill_me else ""
            )
        )
        return 0

    ok = (
        diff.ok
        and not report.unmatched_expectations
        and not (full_run and report.unchecked_contexts)
    )
    if args.as_json:
        print(json.dumps({
            "executables": report.n_executables,
            "violations": [
                _violation_dict(v) for v in report.violations
            ],
            "new": [_violation_dict(v) for v in diff.new],
            "baselined": [_violation_dict(v) for v in diff.baselined],
            "stale": diff.stale,
            "unmatched_expectations": [
                list(t) for t in report.unmatched_expectations
            ],
            "unchecked_contexts": report.unchecked_contexts,
            "ok": ok,
        }, indent=2))
        return 0 if ok else 1

    for v in diff.new:
        print(v.render())
    if diff.baselined:
        print(
            f"d9d-audit: {len(diff.baselined)} baselined violation(s) "
            f"suppressed by {baseline_path}"
        )
    if diff.stale:
        print(
            f"d9d-audit: {len(diff.stale)} stale baseline "
            f"entr{'y' if len(diff.stale) == 1 else 'ies'} no longer "
            "fire(s) — refresh with --write-baseline"
        )
    for context, pattern in report.unmatched_expectations:
        print(
            f"d9d-audit: expectation {context}:{pattern} matched no "
            "captured executable — the contract silently stopped being "
            "checked (renamed executable or dropped leg?)"
        )
    for context in report.unchecked_contexts:
        if full_run:
            print(
                f"d9d-audit: expectation context {context!r} captured "
                "no facts on a FULL harness run — a renamed or dropped "
                "leg must not silently retire its contracts"
            )
        else:
            print(
                f"d9d-audit: note: no facts for expectation context "
                f"{context!r} in this capture (partial run)"
            )
    if diff.new:
        print(
            f"d9d-audit: {len(diff.new)} NEW violation(s) over "
            f"{report.n_executables} captured executable(s) — fix, or "
            "accept into the baseline with --write-baseline + a reason"
        )
    elif ok:
        print(
            f"d9d-audit: clean — {report.n_executables} captured "
            "executable(s) certified"
        )
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
