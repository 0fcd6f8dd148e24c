"""The control: the plain reference put in the program's place with one
stated guarantee broken, and the faults a cell can have.

Two controls, each the step that would tempt a later PR:

- ``info_as_fail``: crashed (``info``) ops are treated as if they had failed.
  Breaks "an info op may take effect at any time from its invoke on": the
  ghost machinery (window slots held for ever, ghost words, subsumption) is
  the dearest part of the crash-heavy search, and dropping it is the cheapest
  way to look fast.
- ``beam``: the search keeps at most ``beam`` configurations and answers
  ``false`` when they die out.  Breaks "unknown, never false, on a degraded
  path": a capped frontier that refutes instead of escalating.

Shared by ``test_benchmark.py`` (toy size, CPU) and ``seeds_on_chip.py``
(the cell's own size, on the chip).
"""

from __future__ import annotations

from typing import Any, Dict

CONTROLS: Dict[str, Dict[str, Any]] = {"info_as_fail": {"info_as_fail": True},
                                       "beam": {"beam": 1}}


def as_program_result(verdicts: Dict[Any, Dict[str, Any]], keyed: bool,
                      analyzer: str, configs: int = 0) -> Dict[str, Any]:
    """Reference-shaped verdicts dressed as the program's result dict, with
    everything but the verdicts as a sound run would have it: so that what
    the comparison fails is the verdict and nothing else."""
    def leaf(v: Dict[str, Any]) -> Dict[str, Any]:
        out = {"valid": v["valid"], "analyzer": analyzer,
               "configs-explored": 0}
        if not v["valid"]:
            out.update(op={"index": v["op_index"]},
                       witness={"valid": False})
        return out
    if not keyed:
        return dict(leaf(verdicts[None]), **{"configs-explored": configs})
    results = {k: leaf(v) for k, v in verdicts.items()}
    if results:
        next(iter(results.values()))["configs-explored"] = configs
    return {"valid": all(v["valid"] for v in verdicts.values()),
            "key-count": len(verdicts), "results": results}
