"""What a run prints: earlier lines (to stdout and to a log file under the
run's output directory), the numbers compared beside their limits on
standard error, and the one JSON object that is the last line of stdout."""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

class Log:
    """Earlier lines: printed at once and kept for the log file."""

    def __init__(self) -> None:
        self.lines: List[str] = []
        self.t0 = time.monotonic()

    def say(self, msg: str) -> None:
        line = f"[bench +{time.monotonic() - self.t0:7.2f}s] {msg}"
        self.lines.append(line)
        print(line, flush=True)

    def write(self, path: str) -> None:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            f.write("\n".join(self.lines) + "\n")


def result_line(correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
                compared: Dict[str, Dict[str, Any]],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    """The last line of stdout.  ``compared`` comes last, each number beside
    its limit."""
    out: Dict[str, Any] = {"correct": bool(correct),
                           "attempted": int(attempted),
                           "failed": int(failed), "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    return json.dumps(out)


def finish(line: str, compared: Dict[str, Dict[str, Any]]) -> None:
    """Compared numbers as the last lines of stderr, the result as the last
    line of stdout."""
    sys.stdout.flush()
    for name, c in compared.items():
        print(f"compared {name} = {c['value']} limit {c['limit']}"
              f"{'' if c['ok'] else '  <-- OUTSIDE'}", file=sys.stderr)
    sys.stderr.flush()
    print(line, flush=True)
