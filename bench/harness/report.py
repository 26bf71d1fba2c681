"""The result line and the correctness checks printed beside it."""
from __future__ import annotations

import json
import math
import sys


def check(value: float, limit: float) -> dict:
    """One compared number; passes when ``value <= limit``."""
    return {"value": float(value), "limit": float(limit),
            "ok": bool(math.isfinite(value) and value <= limit)}


def emit(*, correct: bool, attempted: int, failed: int, metrics: dict,
         units: dict, device: dict, checks: dict, breakdown=None,
         extra: dict | None = None) -> dict:
    """Print the checks to stderr as the last lines there, and the result
    as the last line of stdout, its ``checks`` key last."""
    line = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {k: {"value": float(v), "unit": units[k]}
                    for k, v in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line.update(extra or {})
    line["checks"] = checks
    sys.stdout.flush()
    for name, c in checks.items():
        verdict = "ok" if c.get("ok", True) else "FAIL"
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r}) "
              f"{verdict}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return line
