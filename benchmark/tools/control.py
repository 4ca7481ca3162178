#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``, at a cell's own
size, on the chip: the plain reference put in the program's place and
computed in the nearest precision below the one the configuration states
(fp8 for bfloat16). It has to come out as NOT correct.

    python3 benchmark/tools/control.py --workload <cell> --seeds 1,2,3

Prints, per seed, each number the run compares, as the control reads it,
beside the configuration's limit. Run by hand; the benchmark's own runs do
not run it. ``benchmark/tests`` keeps the same control at a toy size.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--precision", default="fp8")
    args = ap.parse_args(argv)
    from benchmark.harness import spec
    cell = spec.load_cell(args.workload)
    runner = spec.load_plugin("runners", cell.config["runner"])
    for seed in (int(s) for s in args.seeds.split(",")):
        row = runner.control(cell, seed, args.precision)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "precision": args.precision, **row}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
