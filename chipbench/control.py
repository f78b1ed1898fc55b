"""Readings for a cell's limits: the program's, and the control's (the
reference in the precision step below the configuration's), on the
chip at the cell's own size, several seeds in one process.

    python3 chipbench/control.py --workload <name> --seconds <s> \
        --seeds <n> [<n> ...]

Prints one JSON line per seed: the program's readings as served and
the control's on the same prompts and served tokens, each held to the
cell's limits by the harness's own check (`correct`,
`control_correct`). Exits 1 where a control comes out correct. Not
part of a benchmark run.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from chipbench import spec
    from chipbench.run import look_for_chip, run_cell
    cell = spec.load_cell(args.workload)
    peak = look_for_chip(cell.chips)
    passed = 0
    for seed in args.seeds:
        r = run_cell(cell, seed, args.seconds, False, peak, control=True)
        passed += r["control_correct"]
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "control_correct": r["control_correct"],
                          "program": r["readings"],
                          "control": r["control"],
                          "metrics": r["metrics"]}), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
