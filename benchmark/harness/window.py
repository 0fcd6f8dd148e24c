"""The measured window: one call after another for ``seconds``, the call in
flight finishes, wall over calls.  No median, no dropped call, no chunks: a
stall inside the window moves the number."""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List


def run_window(call: Callable[[], Any], seconds: float,
               clock: Callable[[], float] = time.monotonic
               ) -> Dict[str, Any]:
    """Call ``call`` until ``seconds`` have passed since the start, let the
    call in flight finish and stop the clock there.  At least one call."""
    results: List[Any] = []
    ends: List[float] = []
    t0 = clock()
    while True:
        results.append(call())
        now = clock()
        ends.append(now - t0)
        if now - t0 >= seconds:
            break
    wall = ends[-1]
    walls = [b - a for a, b in zip([0.0] + ends[:-1], ends)]
    return {"wall_s": wall, "calls": len(results),
            "per_call_s": wall / len(results), "call_walls_s": walls,
            "results": results}
