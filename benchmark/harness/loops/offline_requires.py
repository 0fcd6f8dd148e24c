"""The offline loop for a cell whose path an older program lacks: the
traffic file's ``requires`` lists ``package.module:attr`` names of the
program, and a program that has not all of them exits non-zero at once,
with no result line, before anything is generated, compiled or measured
(it would answer by another path, one that may take the better part of an
hour, and the cell's ``device_analyzers`` would call the answer a host's
anyway).  Then it is ``harness.loops.offline_plug.run``, unchanged.
"""

from __future__ import annotations

import importlib
from typing import Any

from harness.loops import offline_plug
from harness.manifest import Cell


class Lacking(SystemExit):
    """The program lacks what the cell's traffic file requires."""


def require(names: list) -> None:
    for dotted in names:
        module, attr = dotted.split(":")
        try:
            getattr(importlib.import_module(module), attr)
        except (ImportError, AttributeError) as e:
            raise Lacking(f"benchmark: the program has no {dotted} ({e}); "
                          "nothing measured") from e


def run(cell: Cell, *args: Any, **kw: Any) -> int:
    require(cell.traffic["requires"])
    return offline_plug.run(cell, *args, **kw)
