"""The decomposer: split a request's history into independent cells.

Reuses jepsen_tpu.independent's splitting verbatim: a multi-key history
(every client op's value a ``(key, value)`` tuple, the independent-
workload wire shape) splits into one cell per key with the values
unwrapped — the same per-key sub-histories IndependentChecker would have
checked, so verdicts compose identically (P-compositionality: a history
is linearizable iff every per-key projection is).  Anything else — a
single-register history, an elle transaction history whose anomalies span
keys — stays one cell.

Cells share the request id; the aggregator reassembles them under the
established never-degrade-to-false merge (checker.core.merge_valid).
"""

from __future__ import annotations

from typing import List

from jepsen_tpu.engine import ladder
from jepsen_tpu.history import NEMESIS
from jepsen_tpu.independent import key_of, subhistories
from jepsen_tpu.serve.request import Cell, KIND_ELLE, KIND_WGL, Request


def _engine_identity(req: Request):
    """Everything that changes what a dispatch computes must be part of
    the grouping key — cells sharing a bucket are checked by ONE engine
    call using the group head's spec."""
    if req.kind == KIND_WGL:
        m = req.spec["model"]
        # the fission flag changes the engine a lane runs through
        # (split-and-recombine vs pure ladder), so cells carrying
        # different flags must never share one dispatch group
        return (m.name, m.variant, req.spec.get("fission"))
    return (req.spec.get("workload", "list-append"),
            bool(req.spec.get("realtime", False)),
            req.spec.get("engine", "auto"),
            tuple(req.spec.get("consistency_models") or ()))


def _splittable(req: Request) -> bool:
    """True when every client op carries a key — the independent-workload
    shape.  A partially-keyed history never splits: dropping the keyless
    ops would silently change the verdict."""
    if req.kind != KIND_WGL:
        return False
    saw = False
    for op in req.history:
        if op.process == NEMESIS:
            continue
        if key_of(op) is None:
            return False
        saw = True
    return saw


def decompose(req: Request) -> List[Cell]:
    """Split ``req`` into cells (at least one), bucketed and ready to
    queue.  Sets ``req.cells`` as a side effect."""
    ident = _engine_identity(req)
    if _splittable(req):
        subs = list(subhistories(req.history).items())
    else:
        subs = [(None, req.history)]
    cells = []
    for key, h in subs:
        shape = (ladder.wgl_bucket(h) if req.kind == KIND_WGL
                 else ladder.elle_bucket(h))
        cells.append(Cell(request=req, history=h, key=key,
                          bucket=(req.kind, ident) + shape))
    req.cells = cells
    return cells
