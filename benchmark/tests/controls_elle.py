"""The controls of the ``elle-append10k.offline`` cell, in
``controls.py``'s manner: something with one stated guarantee broken put in
the program's place, and the cell's comparison (the window's answers and
the probes', ``harness.loops.offline_elle``) shown to say "not correct".

- ``realtime_dropped``: the plain reference computed without the realtime
  order (the nearest weaker model below the one the configuration states:
  serializable for strict-serializable) answers the window and the probes.
  Breaks "strict serializability ... and the realtime order": the dense
  realtime layer is a third of the graph's build and the reason the graph
  is dense, and dropping it is the cheapest way to look fast.  On the
  cell's own history its answer is the sound one (the history is valid
  either way); the ``late_reader`` probe is what tells.
- ``overlooked``: every probe answered as the clean history was, "valid, no
  flag set": a closure that never ran, a flag vector of zeros.  Shows that
  one anomaly among 10,000 transactions decides ``correct``.
- ``program_realtime_dropped``: the program itself, its checker built for
  ``serializable`` as ``append_workload`` builds it when asked for nothing
  stronger, run on the history and the probes and judged under the
  configuration's model.  It runs the device path, so it is for toy sizes
  on the CPU; the two above are host Python and answer the cell's own
  10,000 transactions in seconds.

Shared by ``test_elle_append_cell.py`` (toy size and the cell's own).
"""

from __future__ import annotations

import random
from typing import Any, Dict, List

from gen import list_append as la
from harness.loops import offline_elle
from harness.loops.offline import program_history
from harness.manifest import Cell
from reference import elle_list_append as ref

ANALYZER = "elle-tpu"


def as_program_result(want: Dict[str, Any]) -> Dict[str, Any]:
    """A reference-shaped answer dressed as the program's result map, with
    everything but the answer as a sound run would have it."""
    return {"valid": want["valid"],
            "anomaly-types": list(want["anomaly_types"]),
            "device-flags": dict(want["flags"]), "count": want["count"],
            "analyzer": ANALYZER}


def probe_records(cell: Cell, records: List[Any], seed: int) -> List[Any]:
    return [la.CORRUPTORS[name](records, random.Random(seed))
            for name in cell.traffic["probes"]["corruptors"]]


def realtime_dropped(cell: Cell, records: List[Any],
                     seed: int) -> Dict[str, Any]:
    want = ref.check(records, realtime=True)
    weaker = as_program_result(ref.check(records, realtime=False))
    probes = [(as_program_result(ref.check(bad, realtime=False)),
               ref.check(bad, realtime=True))
              for bad in probe_records(cell, records, seed)]
    return offline_elle.compare([weaker], want, want["count"], [ANALYZER],
                                ref.decided, probes)


def overlooked(cell: Cell, records: List[Any], seed: int) -> Dict[str, Any]:
    want = ref.check(records, realtime=True)
    sound = as_program_result(want)
    probes = [(sound, ref.check(bad, realtime=True))
              for bad in probe_records(cell, records, seed)]
    return offline_elle.compare([sound], want, want["count"], [ANALYZER],
                                ref.decided, probes)


def program_realtime_dropped(cell: Cell, records: List[Any],
                             seed: int) -> Dict[str, Any]:
    test = {"name": "control", "checker": offline_elle.program_checker(
        cell.traffic["entry"], ("serializable",))}
    got = test["checker"].check(test, program_history(records))
    probes = offline_elle.probe_answers(test, records, seed, cell.traffic,
                                        ref.check, True)
    return offline_elle.compare([got], ref.check(records, realtime=True),
                                got["count"], cell.config["device_analyzers"],
                                ref.decided, probes)


#: host Python alone: any size
CONTROLS = {"realtime_dropped": realtime_dropped, "overlooked": overlooked}
#: these run the program's device path: toy sizes
PROGRAM_CONTROLS = {"program_realtime_dropped": program_realtime_dropped}
