"""The offline loop for a traffic mix whose generator lives in a module of
its own: ``generator_module`` in the traffic file names ``gen/<module>.py``,
whose ``GENERATORS`` (name -> function, as ``gen.histories`` has them) are
added to ``gen.histories.GENERATORS`` under names that are free (nothing
there is overwritten); then it is ``harness.loops.offline.run``, unchanged.
So a configuration that needs a new generator adds a file under ``gen/`` and
names this loop, and no loop of its own.
"""

from __future__ import annotations

from typing import Any

from gen.histories import GENERATORS
from harness.loops import offline
from harness.manifest import Cell, plugin


def register(traffic: dict) -> None:
    for name, fn in plugin("gen", traffic["generator_module"],
                           "GENERATORS").items():
        GENERATORS.setdefault(name, fn)


def run(cell: Cell, *args: Any, **kw: Any) -> int:
    register(cell.traffic)
    return offline.run(cell, *args, **kw)
