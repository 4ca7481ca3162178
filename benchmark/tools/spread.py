#!/usr/bin/env python3
"""The spread of each end-to-end metric over the runs of one set.

    python3 benchmark/tools/spread.py chiprun_out/<tag> [chiprun_out/<tag> ...]

Reads the result lines of the ``--trace 0`` logs that ``tools/sets.sh``
left under each directory, oldest first, and prints per metric the values,
their median and ``stats.iqr_share`` (quartile distance over the median, the
spread a bound is worked out from: about five times the widest).
"""
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv):
    from benchmark.harness import stats
    for tag in argv:
        values = {}
        for log in sorted(glob.glob(os.path.join(tag, "*_t0_*.log")),
                          key=lambda p: p.rsplit("_", 1)[-1]):
            with open(log) as f:
                lines = f.read().splitlines()
            if not lines or not lines[-1].startswith("{"):
                continue
            line = json.loads(lines[-1])
            cell = os.path.basename(log).split("_s")[0]
            for name, m in line["metrics"].items():
                values.setdefault((cell, name), []).append(m["value"])
        for (cell, name), v in sorted(values.items()):
            spread = stats.iqr_share(v) if len(v) > 1 else float("nan")
            print(json.dumps({"set": tag, "cell": cell, "metric": name,
                              "runs": len(v), "median": stats.median(v),
                              "spread_share": spread, "values": v}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
