#!/usr/bin/env python3
"""Look at one trace by hand: planes, lines, event counts, first events.

    python3 benchmark/tools/describe_trace.py <dir-or-.xplane.pb> [out.json]
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
    from benchmark.harness import trace
    path = argv[0]
    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(
            path, "**", "*.xplane.pb"), recursive=True))[-1]
    text = json.dumps(trace.describe(
        path, sample=int(os.environ.get("SAMPLE", "12")),
        grep=os.environ.get("GREP", "custom-call")), indent=1)
    if len(argv) > 1:
        with open(argv[1], "w") as f:
            f.write(text)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
