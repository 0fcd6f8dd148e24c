"""Consistency-model lattice: which anomalies refute which models.

Parity: elle's ``elle.consistency-model`` as the reference consumes it —
``jepsen/src/jepsen/tests/cycle/append.clj:15-21`` forwards a
``:consistency-models`` option and elle judges validity *relative to those
models*, reporting the weakest models the found anomalies rule out
(``:not`` / ``:also-not``).  The model names and anomaly semantics follow
Adya's portable isolation levels (PL-1 .. PL-3) plus the snapshot-isolation
branch:

- **G0** (write cycle) refutes everything, PL-1 up.
- **G1a/b/c** (aborted read / intermediate read / cyclic information flow)
  refute read-committed (PL-2) up.
- **G-single** (exactly one anti-dependency edge in the cycle) refutes
  consistent-view (PL-2+) and everything above it — including both
  snapshot-isolation and repeatable-read.
- **G-nonadjacent** (>= 2 anti-dependency edges, no two adjacent around the
  cycle) refutes snapshot-isolation: by Fekete's characterization every
  cycle an SI execution admits carries two *consecutive* rw edges, so a
  cycle without such a pair is un-SI-able.  It is also an item-level rw
  cycle, so it refutes repeatable-read.
- **G2-item** (>= 2 rw edges, some adjacent) refutes repeatable-read
  (PL-2.99) and serializability — but NOT snapshot isolation: SI admits
  exactly this shape (write-skew).
- **lost-update** refutes cursor-stability and (via the lattice) SI.
- ``*-realtime`` cycle variants (closable only through a realtime edge)
  refute strict serializability alone.

``boundary`` turns a set of found anomalies into elle's friendly
``{"not", "also-not"}`` report: the weakest refuted models, then every
stronger model they drag down.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Set

#: weaker -> directly-stronger edges of the model lattice.
STRONGER: Dict[str, Set[str]] = {
    "read-uncommitted": {"read-committed"},
    "read-committed": {"monotonic-atomic-view", "cursor-stability"},
    "monotonic-atomic-view": {"consistent-view"},
    "cursor-stability": {"repeatable-read", "snapshot-isolation"},
    "consistent-view": {"repeatable-read", "snapshot-isolation"},
    "repeatable-read": {"serializable"},
    "snapshot-isolation": {"serializable"},
    "serializable": {"strict-serializable"},
    "strict-serializable": set(),
}

CANONICAL = sorted(STRONGER)

ALIASES = {
    "ru": "read-uncommitted", "pl-1": "read-uncommitted",
    "rc": "read-committed", "pl-2": "read-committed",
    "mav": "monotonic-atomic-view",
    "pl-2+": "consistent-view",
    "pl-cs": "cursor-stability",
    "rr": "repeatable-read", "pl-2.99": "repeatable-read",
    "si": "snapshot-isolation",
    "ser": "serializable", "serializability": "serializable",
    "pl-3": "serializable", "1sr": "serializable",
    "strict-1sr": "strict-serializable", "pl-ss": "strict-serializable",
    "strong-serializable": "strict-serializable",
    "linearizable": "strict-serializable",
}

#: anomaly type -> the weakest model(s) it directly refutes.  Stronger
#: models fall via the lattice (``implied``).
ANOMALY_REFUTES: Dict[str, Set[str]] = {
    "G0": {"read-uncommitted"},
    "duplicate-appends": {"read-uncommitted"},
    "duplicate-writes": {"read-uncommitted"},
    "cyclic-versions": {"read-uncommitted"},
    "G1a": {"read-committed"},
    "G1b": {"read-committed"},
    "G1c": {"read-committed"},
    "incompatible-order": {"read-committed"},
    "G-single": {"consistent-view"},
    "lost-update": {"cursor-stability"},
    "G-nonadjacent": {"snapshot-isolation", "repeatable-read"},
    "G2-item": {"repeatable-read"},
    "G2": {"serializable"},
    # cycles that need a realtime edge to close refute only the strict tier
    "G0-realtime": {"strict-serializable"},
    "G1c-realtime": {"strict-serializable"},
    "G-single-realtime": {"strict-serializable"},
    "G-nonadjacent-realtime": {"strict-serializable"},
    "G2-item-realtime": {"strict-serializable"},
}


def canonicalize(model: str) -> str:
    m = model.strip().lower()
    m = ALIASES.get(m, m)
    if m not in STRONGER:
        raise ValueError(f"unknown consistency model {model!r}; "
                         f"known: {CANONICAL}")
    return m


def needs_realtime(models: Optional[Iterable[str]]) -> bool:
    """Does judging these models take the realtime order into the graph?
    Only strict serializability (under any of its ``ALIASES``) speaks of
    it; ``None`` is the checkers' default, serializable."""
    return any(canonicalize(m) == "strict-serializable"
               for m in models or ())


def implied(models: Iterable[str]) -> Set[str]:
    """Upward closure: every model at least as strong as one of ``models``
    (a violation of a weak model refutes all stronger ones)."""
    out: Set[str] = set()
    stack = [canonicalize(m) for m in models]
    while stack:
        m = stack.pop()
        if m not in out:
            out.add(m)
            stack.extend(STRONGER[m])
    return out


def refuted_models(anomaly_types: Iterable[str]) -> Set[str]:
    """All models (closure) the given anomaly types rule out.  Unknown
    anomaly types (workload-specific internal checks) refute everything —
    conservative, like elle treating unclassified anomalies as fatal."""
    direct: Set[str] = set()
    for a in anomaly_types:
        direct |= ANOMALY_REFUTES.get(a, {"read-uncommitted"})
    return implied(direct) if direct else set()


def boundary(anomaly_types: Iterable[str]) -> Dict[str, List[str]]:
    """Elle's friendly boundary: ``not`` = the weakest refuted models (no
    refuted model weaker than them), ``also-not`` = the rest of the refuted
    closure."""
    refuted = refuted_models(anomaly_types)
    not_ = {m for m in refuted
            if not any(m in implied([o]) for o in refuted if o != m)}
    return {"not": sorted(not_), "also-not": sorted(refuted - not_)}


def judge(consistency_models: Sequence[str],
          anomaly_types: Iterable[str]) -> bool:
    """True iff none of the requested models is refuted by the anomalies."""
    wanted = {canonicalize(m) for m in consistency_models}
    return not (wanted & refuted_models(anomaly_types))
