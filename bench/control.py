"""Read a cell's compared numbers for the program and for its controls,
over several seeds in one process, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

Each seed runs the cell as ``run.py`` does, then reads every control
that the configuration's reference names (``CONTROLS``): the reference
computed one precision below what the configuration states, put in the
program's place.  One JSON line per seed: the program's numbers
(``compared``) and each control's.  The benchmark's own runs never read
the controls; the limits in ``bench/reference`` are set from these
readings.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    from benchlib import files, harness
    _, cfg_entry = files.cell(files.benchmark(), args.workload)
    ref = files.module("reference", cfg_entry["name"])
    for seed in [int(s) for s in args.seeds.split(",")]:
        try:
            r = harness.run_cell(args.workload, seed, args.seconds, False,
                                 time.monotonic(),
                                 controls=tuple(ref.CONTROLS))
        except harness.NoChip as e:
            harness.note(f"control: {e}")
            return 3
        line = {"workload": args.workload, "seed": seed,
                "correct": r["correct"], "failed": r["failed"],
                "metrics": {k: v["value"] for k, v in r["metrics"].items()},
                "program": {c["name"]: c["value"] for c in r["compared"]},
                "controls": r["controls"]}
        print("CONTROL " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
