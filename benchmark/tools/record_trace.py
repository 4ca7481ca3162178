#!/usr/bin/env python3
"""Cut a small recorded trace for ``benchmark/tests/data``.

    python3 benchmark/tools/record_trace.py <trace-dir> <out.json> [ops]

Keeps the first ``ops`` (default 400) device operations of the first device
after the window opens, the programs and host events that overlap them, and
a ``bench:window`` that ends with the last kept operation. Beside the events
it writes the numbers expected from them, worked out here by an independent
method (a per-nanosecond occupancy array), which the test compares with the
reduction in ``harness/trace.py``.
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
    import numpy as np
    from benchmark.harness import trace
    src, out = argv[0], argv[1]
    keep = int(argv[2]) if len(argv) > 2 else 400
    path = sorted(glob.glob(os.path.join(src, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    ev = trace.load(path)
    lo, _hi = trace.window(ev)
    first = sorted(ev["devices"])[0]
    ops = [e for e in ev["devices"][first]["ops"] if e[1] >= lo][:keep]
    hi = max(e[1] + e[2] for e in ops)
    inside = lambda e: e[1] < hi and e[1] + e[2] > lo
    small = {"devices": {first: {
        "ops": ops,
        "modules": [e for e in ev["devices"][first]["modules"]
                    if inside(e)]}},
        "host": [[trace.WINDOW, lo, hi - lo, "main"]] + [
            e for e in ev["host"] if e[0] != trace.WINDOW and inside(e)
            and e[2] > 20_000][:400]}
    occupied = np.zeros(hi - lo, bool)
    by_kind = {}
    for name, start, dur in ops:
        a, b = max(start, lo) - lo, min(start + dur, hi) - lo
        occupied[a:b] = True
        k = trace.op_kind(name)
        by_kind[k] = by_kind.get(k, 0) + (b - a)
    expected = {"busy_s": float(occupied.sum()) / 1e9,
                "window_s": (hi - lo) / 1e9,
                "top_ops": [k for k, _ in sorted(
                    by_kind.items(), key=lambda kv: -kv[1])[:3]]}
    with open(out, "w") as f:
        json.dump({"from": os.path.basename(path), "events": small,
                   "expected": expected}, f)
    print(json.dumps(expected))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
