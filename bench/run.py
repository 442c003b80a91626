"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
        --trace <0|1>

The cell, its configuration and its traffic are read from BENCHMARK.json
and the files it names.  The run needs a TPU: without one it exits with
code 3 and prints no result.  The last line of standard output is the
result object; the numbers compared for ``correct`` are also the last
lines of standard error, each beside its limit.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    from benchlib import harness
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), T_START)
    except harness.NoChip as e:
        harness.note(f"bench: {e}")
        return 3
    except FileNotFoundError as e:
        harness.note(f"bench: {e}")
        return 2
    for c in result["compared"]:
        harness.note(f"check {c['name']}: {c['value']!r} "
                     f"limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
