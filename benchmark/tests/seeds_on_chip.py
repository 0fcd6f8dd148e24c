#!/usr/bin/env python3
"""Many seeds of one cell, and the controls, in one process on the chip.

  python3 benchmark/tests/seeds_on_chip.py --workload <cell> --seeds 1,2,3 \
      [--structures 11,12]

For each seed: the cell's history at its own size, one ``core.analyze`` (the
window's own call), the plain reference, the comparison that decides
``correct``; then each control of ``controls.py`` in the program's place,
which has to come out not correct.  ``--structures`` also tries other
``history_seed``s than the traffic file's (other searches, so other engine
shapes: each may compile).  One JSON line a seed; exit 1 if a program run
was not correct or a control was.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH), HERE]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--structures", default="")
    args = ap.parse_args(argv)
    from controls import CONTROLS, as_program_result
    from gen.histories import GENERATORS
    from harness import correct, device
    from harness.loops import offline
    from harness.manifest import Cell
    cell = Cell(args.workload)
    device.require_tpu(cell.chips)
    from jepsen_tpu import core
    from jepsen_tpu.obs.hist import compile_hist_stats
    from jepsen_tpu.ops.cache import init_compilation_cache
    init_compilation_cache()
    analyzers = cell.config["device_analyzers"]
    checker = offline.program_checker(cell.traffic["entry"],
                                      cell.config["model"])
    structures = [int(s) for s in args.structures.split(",") if s] \
        or [cell.traffic["params"]["history_seed"]]
    bad = 0
    for structure in structures:
        cell.traffic["params"]["history_seed"] = structure
        for seed in (int(s) for s in args.seeds.split(",")):
            gen = GENERATORS[cell.traffic["generator"]](
                cell.config, cell.traffic["params"], seed)
            history = offline.program_history(gen["records"])
            t0 = time.monotonic()
            res = core.analyze({"name": cell.name, "checker": checker},
                               history)
            wall = time.monotonic() - t0
            configs = correct.configs_explored(res, gen["keyed"])
            t0 = time.monotonic()
            want = offline.reference_verdicts(cell, gen)
            ref_s = time.monotonic() - t0
            got = correct.compare([res], want, gen["keyed"], configs,
                                  analyzers)
            line = {"workload": cell.name, "structure": structure,
                    "seed": seed, "call_s": wall, "reference_s": ref_s,
                    "configs_explored": configs,
                    "refuted": sum(not w["valid"] for w in want.values()),
                    "correct": got["correct"],
                    "compared": {k: v["value"]
                                 for k, v in got["compared"].items()},
                    "shapes": len(compile_hist_stats()), "controls": {}}
            bad += not got["correct"]
            for name, kw in CONTROLS.items():
                verdicts = offline.reference_verdicts(cell, gen, **kw)
                fake = as_program_result(verdicts, gen["keyed"],
                                         analyzers[-1], configs)
                c = correct.compare([fake], want, gen["keyed"], configs,
                                    analyzers)
                line["controls"][name] = {
                    "correct": c["correct"],
                    "verdict_mismatches":
                        c["compared"]["verdict_mismatches"]["value"],
                    "merged_mismatches":
                        c["compared"]["merged_mismatches"]["value"]}
                bad += c["correct"]
            print(json.dumps(line), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
