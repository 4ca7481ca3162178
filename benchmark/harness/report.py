"""What a run prints: notes on earlier lines, the result as the last."""
from __future__ import annotations

import json
import sys
from typing import Any, Dict, List


def note(tag: str, **fields: Any) -> None:
    """One earlier line of standard output, as JSON after a ``#`` tag, so
    that a noisy run can be read from its log."""
    print(f"# {tag} {json.dumps(fields, sort_keys=True)}", flush=True)


class Checks:
    """Every number compared beside its limit, an upper bound; ``correct``
    is all of them."""

    def __init__(self):
        self.rows: List[Dict[str, Any]] = []

    def add(self, name: str, value: float, limit: float) -> bool:
        value = float(value)
        ok = bool(value <= limit)                  # NaN is never correct
        self.rows.append({"name": name, "value": value, "limit": limit,
                          "kind": "at_most", "ok": ok})
        return ok

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def print(self) -> None:
        for r in self.rows:
            note("check", **r)


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
                breakdown: Dict[str, Any] | None = None) -> str:
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    return json.dumps(line)


def emit(line: str) -> None:
    sys.stdout.write(line + "\n")
    sys.stdout.flush()
