#!/usr/bin/env python3
"""The benchmark's one command.

  python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on, in this
one process, and prints as the last line of stdout one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device`` and, traced, ``breakdown``; then ``compared``, the numbers that
decided ``correct``, each beside its limit.  Exits non-zero with no result
line when JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)                      # harness, gen, readers, ...
sys.path.insert(1, os.path.dirname(HERE))     # the program, jepsen_tpu


def process_age_s() -> float:
    """How long this process had lived when ``_T0`` was read: interpreter
    start-up belongs to set-up too.  0 where /proc cannot say."""
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime", encoding="ascii") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return max(0.0, age - (time.monotonic() - _T0))
    except (OSError, ValueError, IndexError):
        return 0.0


def main(argv=None) -> int:
    t_start = _T0 - process_age_s()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    from harness import report
    from harness.manifest import Cell, plugin
    cell = Cell(args.workload)
    loop = plugin("harness.loops", cell.traffic["loop"], "run")
    return loop(cell, args.seed, args.seconds, bool(args.trace), t_start,
                report.Log())


if __name__ == "__main__":
    sys.exit(main())
