"""The comparison that decides ``correct``: every verdict the window's calls
gave, against the plain reference's verdict on the same records.

A verdict here is what the user reads: valid or not, the refuting op, per key
where the history is keyed; and it has to come from the device path
(``analyzer`` is one of the configuration's ``device_analyzers``, no
``fallback-chain``), carry a host-confirmed
witness when it refutes, and be ``unknown`` never.  Every comparison is
exact, so every limit is 0.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List, Optional, Sequence

#: analyzers and solvers that mean a host tier answered for the device
HOST_TIERS = ("wgl-cpu", "elle-cpu", "linear-cpu")


def walk(res: Any) -> Iterator[Dict[str, Any]]:
    """Every dict nested in a result but the witness (a host re-derivation
    by design): per-key results ride inside the aggregate."""
    if isinstance(res, dict):
        yield res
        for k, v in res.items():
            if k != "witness":
                yield from walk(v)
    elif isinstance(res, (list, tuple)):
        for v in res:
            yield from walk(v)


def leaves(res: Dict[str, Any], keyed: bool) -> Dict[Any, Dict[str, Any]]:
    """key -> that key's result (``None`` -> the result itself)."""
    if keyed:
        return dict(res.get("results") or {})
    return {None: res}


def configs_explored(res: Dict[str, Any], keyed: bool) -> int:
    return sum(int(r.get("configs-explored", 0))
               for r in leaves(res, keyed).values())


def host_answers(res: Dict[str, Any], keyed: bool,
                 analyzers: Sequence[str]) -> int:
    """How many of this call's verdicts did not come from the device path:
    leaves whose ``analyzer`` is none of the configuration's device
    analyzers, and one more if a fallback or a host solver shows anywhere
    in the result."""
    n = sum(r.get("analyzer") not in analyzers
            for r in leaves(res, keyed).values())
    for d in walk(res):
        if "fallback-chain" in d or "fallback" in d \
                or d.get("solver") in HOST_TIERS \
                or d.get("analyzer") in HOST_TIERS:
            return n + 1
    return n


def same_verdict(got: Dict[str, Any], want: Dict[str, Any]) -> bool:
    if got.get("valid") is not want["valid"]:
        return False
    if want["valid"]:
        return True
    op = got.get("op")
    return isinstance(op, dict) and op.get("index") == want["op_index"]


def compare(results: List[Dict[str, Any]], want: Dict[Any, Dict[str, Any]],
            keyed: bool, warm_configs: Optional[int],
            analyzers: Sequence[str]) -> Dict[str, Any]:
    """Numbers compared, each with its limit, and the contract's counts.
    ``want`` is the reference's verdict per key (``None`` for one history).
    """
    n = {"verdict_mismatches": 0, "unknown_verdicts": 0, "host_answers": 0,
         "missing_witnesses": 0, "configs_drift": 0, "merged_mismatches": 0}
    attempted = failed = 0
    merged = all(w["valid"] for w in want.values())
    for res in results:
        got = leaves(res, keyed)
        n["host_answers"] += host_answers(res, keyed, analyzers)
        if warm_configs is not None \
                and configs_explored(res, keyed) != warm_configs:
            n["configs_drift"] += 1
        if keyed and (res.get("valid") is not merged
                      or res.get("key-count") != len(want)):
            n["merged_mismatches"] += 1
        for k, w in want.items():
            attempted += 1
            r = got.get(k)
            if r is None or r.get("valid") not in (True, False):
                n["unknown_verdicts"] += 1
                failed += 1
                continue
            if not same_verdict(r, w):
                n["verdict_mismatches"] += 1
                failed += 1
            elif r["valid"] is False and not (
                    isinstance(r.get("witness"), dict)
                    and r["witness"].get("valid") is False):
                n["missing_witnesses"] += 1
                failed += 1
    compared = {k: {"value": v, "limit": 0, "ok": v == 0}
                for k, v in n.items()}
    return {"correct": all(c["ok"] for c in compared.values())
            and attempted > 0,
            "attempted": attempted, "failed": failed, "compared": compared}
