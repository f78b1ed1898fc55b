"""Regression gate: fail CI only on *new* test failures.

Runs the tier-1 suite (no -x, so the full failure set is visible),
diffs the failed test ids against a recorded known-failure baseline,
and exits nonzero iff a test outside the baseline failed. Baseline
entries that now pass are "stale": the default (CI) mode fails on them
too — the ratchet only moves forward, forcing a baseline prune commit —
while `--update` rewrites the baseline to the current failure set
(pruning fixed tests, recording triaged new ones).

The baseline holds the known failure set (`failures`) and the
collected-test floor (`min_collected`): the gate fails when fewer tests
are collected than the floor, so a whole test file silently dropping
out of collection (an import-guard skip, a renamed module) is a gated
regression too — new suites join the ratchet by re-recording the floor
with --update.

  python scripts/check_regressions.py                 # gate (CI)
  python scripts/check_regressions.py --update        # re-record
  python scripts/check_regressions.py --allow-stale   # warn, don't fail
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import tempfile
import xml.etree.ElementTree as ET

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _ratchet import diff_ratchet, dump_json, load_json  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(REPO, "tests", "known_failures.json")


def run_pytest(extra: list) -> tuple:
    """Run the suite, return (failed_ids, n_collected). Uses junit xml
    so collection errors surface as failures too."""
    with tempfile.TemporaryDirectory() as td:
        xml_path = os.path.join(td, "report.xml")
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(REPO, "src") + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        cmd = [sys.executable, "-m", "pytest", "-q",
               f"--junitxml={xml_path}"] + extra
        r = subprocess.run(cmd, cwd=REPO, env=env)
        if not os.path.exists(xml_path):
            print(f"pytest produced no junit xml (exit {r.returncode})",
                  file=sys.stderr)
            sys.exit(2)
        # 0 = all passed, 1 = some tests failed (the diff handles it).
        # Anything else (interrupted / internal error / usage / no
        # tests) means the junit xml may be partial — never treat a
        # partially-run suite as green.
        if r.returncode not in (0, 1):
            print(f"pytest did not run to completion (exit "
                  f"{r.returncode}); refusing to diff a partial suite",
                  file=sys.stderr)
            sys.exit(2)
        root = ET.parse(xml_path).getroot()
        failed, total = set(), 0
        for case in root.iter("testcase"):
            total += 1
            nodeid = f"{case.get('classname', '')}::{case.get('name', '')}"
            if case.find("failure") is not None \
                    or case.find("error") is not None:
                failed.add(nodeid)
        return failed, total


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--update", action="store_true",
                    help="rewrite the baseline to the current "
                         "failure set")
    ap.add_argument("--allow-stale", action="store_true",
                    help="fixed baseline entries warn instead of fail")
    ap.add_argument("pytest_args", nargs="*",
                    help="extra args forwarded to pytest (after --)")
    args = ap.parse_args()

    failed, total = run_pytest(args.pytest_args)
    baseline = load_json(args.baseline, default={})
    known = set(baseline.get("failures", []))
    # the collected floor only means anything for a full-suite run:
    # forwarded pytest args select a subset, which must neither trip
    # the shrink gate nor re-record a tiny floor
    full_suite = not args.pytest_args
    floor = int(baseline.get("min_collected", 0)) if full_suite else 0

    new, stale = diff_ratchet(failed, known)
    print(f"\n[check_regressions] {total} tests, "
          f"{len(failed)} failed ({len(known)} known, "
          f"collected floor {floor})")

    if args.update:
        baseline["failures"] = sorted(failed)
        if full_suite:
            baseline["min_collected"] = total
        dump_json(args.baseline, baseline)
        print(f"[check_regressions] baseline <- "
              f"{len(failed)} entries, min_collected <- {total} "
              f"({args.baseline})")
        return 0

    rc = 0
    if total < floor:
        print(f"[check_regressions] suite SHRANK: {total} collected < "
              f"recorded floor {floor} — a test file stopped being "
              f"collected (import error, renamed module?); re-record "
              f"with --update only if intentional")
        rc = 1
    if new:
        print(f"[check_regressions] {len(new)} NEW failure(s):")
        for t in new:
            print(f"  + {t}")
        rc = 1
    if stale:
        print(f"[check_regressions] {len(stale)} baseline entr"
              f"{'y is' if len(stale) == 1 else 'ies are'} now passing "
              f"— prune with --update:")
        for t in stale:
            print(f"  - {t}")
        if not args.allow_stale:
            rc = 1
    if rc == 0:
        print("[check_regressions] OK: no new failures, baseline tight")
    return rc


if __name__ == "__main__":
    sys.exit(main())
