"""The control, on the chip at a cell's own size (not part of a run).

    python3 perf/control.py --workload <cell> --seeds 11,12,13 --seconds 10

For each seed it makes one run of the cell and prints, as one JSON line,
the numbers the program's links gave and the numbers the control gave:
the reference computed in float32 put in the program's place.  The
control has to read not correct; the program's readings over many seeds
and the control's set each limit (PERF.md)."""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402
from compare import is_correct  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=int, required=True)
    args = parser.parse_args()
    for seed in map(int, args.seeds.split(",")):
        result = run.run_cell(args.workload, seed, args.seconds, False,
                              control=True)
        control = result.pop("control")
        print(json.dumps({
            "seed": seed, "program": result["compared"],
            "program_correct": result["correct"],
            "control": {k: v for k, (v, _) in control.items()},
            "control_correct": is_correct(control),
            "metrics": result["metrics"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
