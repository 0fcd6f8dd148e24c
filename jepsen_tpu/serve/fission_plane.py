"""Hydra — the distributed-fission plane: fan one giant history across
the whole fleet.

Engine fission (engine.fission, PR 11) splits an overflowing WGL search
into independent sub-problems — per-key component projections
(arXiv 1504.00204) and ghost case-splits (arXiv 2410.04581) — but
recombines them *inside one worker*, so the capacity ceiling merely
moved from "one device" to "one host".  This plane applies the same two
splitters at the **fleet edge**: when a WGL cell's event count crosses
the fleet-fission threshold at admission, :func:`scatter` decomposes it
into first-class child cells that ride the existing machinery
unchanged — the rendezvous router places each sub-problem on its own
worker (distinct cell ids → distinct route tokens), mesh-aware
placement, hedging, circuit breakers, lease-eviction reroute and the
FleetJournal all apply *per sub-problem*, so a worker SIGKILL
mid-search re-runs only the sub-problems that worker owned.

Recombination happens in serve.aggregate under the exact
unknown-never-false table from docs/fission.md, with one discipline
*stricter* than the engine's: a distributed ``False`` must carry the
refuting sub-problem's op **and** witness, else the group degrades to
unknown — a lost worker can cost a refutation, never fabricate one.
:func:`on_child_result` enforces the evidence half of that contract at
the finalize seam: a refuting child that arrived witness-less gets one
witness-recovery re-check dispatched **only to the worker that produced
the refutation** (its engine cache is the only warm one), and siblings
whose group is already decided are cancelled at the fleet edge (the
drive loop stops re-dispatching; a worker mid-compute is never
interrupted — its verdict is simply ignored).

The one-giant-component case — nothing to scatter — is not this
plane's job: the worker-local fission path now ends in the
window-shrinking recursion (engine.shrink) instead of an escalation to
a capacity no worker has.

Knobs (README env table): ``JTPU_FLEETFISSION`` (default on),
``JTPU_FLEETFISSION_THRESHOLD`` (default 8192 events — the admission
event count past which a cell scatters), and
``JTPU_FLEETFISSION_MAX_SUBPROBLEMS`` (default 256 — a cell that would
need more children stays whole and is the worker's problem).
"""

from __future__ import annotations

import itertools
import logging
import os
import threading
import time
from typing import Any, Dict, List, Optional, TYPE_CHECKING

from jepsen_tpu.engine import fission as engine_fission, ladder
from jepsen_tpu.obs.hist import HistogramSet
from jepsen_tpu.obs.recorder import RECORDER
from jepsen_tpu.serve.decompose import _engine_identity
from jepsen_tpu.serve.metrics import mono_now
from jepsen_tpu.serve.request import Cell, KIND_WGL, Request

if TYPE_CHECKING:  # pragma: no cover
    from jepsen_tpu.serve.fleet import Fleet

log = logging.getLogger("jepsen_tpu.serve.fission_plane")

ANALYZER = "fleet-fission"

DEFAULT_THRESHOLD = 8192
DEFAULT_MAX_SUBPROBLEMS = 256

#: Bound on one witness-recovery re-check (further clamped by the
#: request's remaining deadline budget).
RECOVERY_WAIT_S = 30.0

_gids = itertools.count(1)

#: Sub-problem turnaround (admission → finalize) histogram, merged into
#: the /metrics fission section.
HISTS = HistogramSet()


# ---------------------------------------------------------------------------
# Knobs
# ---------------------------------------------------------------------------

def fleetfission_enabled() -> bool:
    return os.environ.get("JTPU_FLEETFISSION", "1").lower() \
        not in ("0", "false", "no", "off", "")


def fleetfission_threshold() -> int:
    """Admission event count past which a WGL cell scatters fleet-wide."""
    try:
        return max(1, int(os.environ.get("JTPU_FLEETFISSION_THRESHOLD",
                                         DEFAULT_THRESHOLD)))
    except ValueError:
        return DEFAULT_THRESHOLD


def fleetfission_max_subproblems() -> int:
    try:
        return max(2, int(os.environ.get("JTPU_FLEETFISSION_MAX_SUBPROBLEMS",
                                         DEFAULT_MAX_SUBPROBLEMS)))
    except ValueError:
        return DEFAULT_MAX_SUBPROBLEMS


# ---------------------------------------------------------------------------
# Counters (serve idiom: hyphenated keys, exported in /metrics "fission")
# ---------------------------------------------------------------------------

_STATS_LOCK = threading.Lock()


def _zero_stats() -> Dict[str, int]:
    return {"scattered": 0, "remote-subproblems": 0, "cancelled": 0,
            "witness-recoveries": 0, "witness-recovery-failures": 0}


_STATS = _zero_stats()


def plane_stats() -> Dict[str, int]:
    """Fleet-edge fission counters: cells scattered, child cells created,
    siblings early-cancelled, witness recoveries run and failed."""
    with _STATS_LOCK:
        return dict(_STATS)


def reset_plane_stats() -> None:
    with _STATS_LOCK:
        _STATS.update(_zero_stats())


def _bump(**kw: int) -> None:
    with _STATS_LOCK:
        for k, v in kw.items():
            _STATS[k] += v


# ---------------------------------------------------------------------------
# Scatter: admission-time decomposition into first-class fleet cells
# ---------------------------------------------------------------------------

def cancelled_result() -> Dict[str, Any]:
    """What a cancelled sub-problem resolves to: unknown, never false —
    a sibling already decided the group, so this verdict is vestigial
    and the recombiner's any-False / any-True rules ignore it."""
    return {"valid": "unknown", "analyzer": ANALYZER, "cancelled": True,
            "error": "sub-problem cancelled: a sibling already decided "
                     "the fission group"}


def scatter(req: Request) -> List[Cell]:
    """Replace each over-threshold WGL cell in ``req.cells`` with
    fission child cells (component projections, else ghost variants);
    cells that don't qualify — or whose split fails for any reason —
    pass through untouched: scatter degrades to "the worker's problem",
    never to a lost cell.  Returns the new ``req.cells``."""
    if req.kind != KIND_WGL or not fleetfission_enabled() \
            or req.spec.get("fission") is False:
        return req.cells
    thr = fleetfission_threshold()
    out: List[Cell] = []
    for cell in req.cells:
        if len(cell.history.ops) < thr:
            out.append(cell)
            continue
        try:
            children = _split_cell(req, cell)
        except Exception as e:  # noqa: BLE001 — scatter must never lose a cell
            log.exception("fleet fission split failed; cell stays whole")
            RECORDER.record("fission", "scatter-error",
                            args={"error": f"{type(e).__name__}: {e}"})
            children = None
        if not children:
            out.append(cell)
            continue
        _bump(scattered=1)
        _bump(**{"remote-subproblems": len(children)})
        RECORDER.record("fission", "scatter", trace_id=req.trace_id,
                        span_id=req.span_id,
                        args={"group": children[0].fission["group"],
                              "mode": children[0].fission["mode"],
                              "subproblems": len(children),
                              "events": len(cell.history.ops)})
        out.extend(children)
    req.cells = out
    return out


def _split_cell(req: Request, cell: Cell) -> Optional[List[Cell]]:
    """One cell → fission children, or None when neither splitter
    applies within the sub-problem cap (one giant component AND too many
    ghosts: the worker-local shrink recursion is the remaining tool)."""
    model = req.spec["model"]
    max_subs = fleetfission_max_subproblems()
    subs = engine_fission.component_split(model, cell.history)
    if subs is not None and len(subs) >= 2 and len(subs) <= max_subs:
        # Component children keep worker-local fission ON: an exceeded
        # projection re-splits inside its worker (ghost re-resolve),
        # exactly as _check_components does for exceeded lanes.
        return _make_children(req, cell, "components", subs, overrides={})
    h = cell.history.client_ops()
    ghosts = engine_fission._real_ghosts(model, h)
    if not ghosts or (1 << len(ghosts)) > max_subs:
        return None
    k = len(ghosts)
    variants = [engine_fission.ghost_variant(h, ghosts, m)
                for m in range(1 << k)]
    # Every variant is ghost-free, so each worker checks it lean at a
    # threshold-sized ceiling — the same shape engine._ghost_split
    # dispatches, which is what lane-for-lane parity is measured against.
    wthr = engine_fission.fission_threshold()
    return _make_children(req, cell, "ghosts", variants,
                          overrides={"fission": False,
                                     "capacity": min(256, wthr),
                                     "max_capacity": wthr})


def _make_children(req: Request, parent: Cell, mode: str, subs: List,
                   overrides: Dict[str, Any]) -> List[Cell]:
    gid = f"{req.id}.g{next(_gids)}"
    ident = _engine_identity(req)
    now = mono_now()
    return [Cell(request=req, history=sub, key=parent.key,
                 bucket=(req.kind, ident) + ladder.wgl_bucket(sub),
                 enqueued=now,
                 fission={"group": gid, "mode": mode, "index": i,
                          "subproblems": len(subs)},
                 spec_overrides=dict(overrides))
            for i, sub in enumerate(subs)]


# ---------------------------------------------------------------------------
# Finalize seam: evidence discipline + sibling cancel
# ---------------------------------------------------------------------------

def on_child_result(fleet: "Fleet", cell: Cell,
                    result: Dict[str, Any]) -> Dict[str, Any]:
    """Called by the fleet as each cell's verdict lands, *before* the
    cell is finalized.  Ordinary cells pass through.  For fission
    children: observe turnaround, enforce the evidence contract on
    refutations (witness recovery on the refuting worker only, degrade
    to unknown on failure — never fabricate False), and early-cancel
    siblings once this child decides the group."""
    if cell.fission is None:
        return result
    if cell.enqueued:
        HISTS.observe("fleetfission:subproblem-s",
                      mono_now() - cell.enqueued)
    mode = cell.fission["mode"]
    index = cell.fission["index"]
    v = result.get("valid")
    # The evidence-bearing refutation sites: a components child's False
    # decides the group; a ghosts child's False only matters as evidence
    # when it is the all-elided branch (index 0), whose op/witness are
    # the canonical ones for the all-False conjunction.
    bears_evidence = (mode == "components" and v is False) \
        or (mode == "ghosts" and v is False and index == 0)
    if bears_evidence and not ("op" in result and "witness" in result):
        result = _recover_witness(fleet, cell, result)
        v = result.get("valid")
    decides = (mode == "components" and v is False
               and "op" in result and "witness" in result) \
        or (mode == "ghosts" and v is True)
    if decides:
        _cancel_siblings(fleet, cell)
    return result


def _recover_witness(fleet: "Fleet", cell: Cell,
                     result: Dict[str, Any]) -> Dict[str, Any]:
    """A refuting child arrived witness-less (witness budget, wire
    truncation).  Re-check the sub-history on the SAME worker that
    refuted it — the only one with a warm engine cache for this shape —
    and adopt its op/witness.  Any failure (worker dead, re-check
    unknown, deadline) degrades this child's False to unknown: the
    distributed table refuses an unwitnessed False, so a lost worker
    can lose a refutation but can never fabricate one."""
    req = cell.request
    wid = (result.get("fleet") or {}).get("worker")
    worker = next((w for w in fleet.workers if w.wid == wid), None)
    _bump(**{"witness-recoveries": 1})
    t0 = mono_now()
    recovered: Optional[Dict[str, Any]] = None
    why = "refuting worker not found"
    if worker is not None and worker.alive():
        try:
            recovered = _recheck_on(worker, cell)
        except Exception as e:  # noqa: BLE001 — recovery is best-effort
            why = f"witness re-check failed: {type(e).__name__}: {e}"
    elif worker is not None:
        why = f"refuting worker w{wid} died before witness recovery"
    RECORDER.record("fission", "witness-recovery", trace_id=req.trace_id,
                    span_id=req.span_id, dur_s=mono_now() - t0,
                    args={"group": cell.fission["group"], "worker": wid,
                          "ok": bool(recovered)})
    if recovered is not None and recovered.get("valid") is False \
            and "op" in recovered and "witness" in recovered:
        # witness: re-derived on the refuting worker from the same sub-history; False keeps its evidence
        out = dict(result)
        out["op"] = recovered["op"]
        out["witness"] = recovered["witness"]
        out.setdefault("fission", {})
        if isinstance(out["fission"], dict):
            out["fission"]["witness-recovered"] = True
        return out
    if recovered is not None:
        why = (f"witness re-check did not re-refute "
               f"(valid={recovered.get('valid')!r})")
    _bump(**{"witness-recovery-failures": 1})
    return {"valid": "unknown", "analyzer": ANALYZER,
            "error": f"unwitnessed refutation degraded to unknown: {why}",
            "configs-explored": int(result.get("configs-explored", 0) or 0),
            "fleet": dict(result.get("fleet") or {})}


def _recheck_on(worker, cell: Cell) -> Optional[Dict[str, Any]]:
    """One bounded explain=True re-check of ``cell`` on ``worker``."""
    req = cell.request
    from jepsen_tpu.serve.service import submit_kwargs
    kw = submit_kwargs(req)
    kw.update(cell.spec_overrides)
    kw["explain"] = True
    rem = req.remaining_s()
    cap = RECOVERY_WAIT_S if rem is None else max(0.0, min(rem,
                                                           RECOVERY_WAIT_S))
    wreq = worker.service.submit(cell.history, block=False,
                                 deadline_s=rem,
                                 trace=req.trace_context(), **kw)
    deadline = mono_now() + cap
    while mono_now() < deadline:
        if wreq.done():
            return dict(wreq.result or {})
        if not worker.alive():
            return None
        time.sleep(0.02)
    return None


def _cancel_siblings(fleet: "Fleet", cell: Cell) -> None:
    """Flag every still-unresolved sibling in this cell's fission group:
    the drive loop stops (re-)dispatching them and they finalize as
    :func:`cancelled_result`.  A worker already computing one is never
    interrupted — its verdict just stops mattering (the recombiner's
    any-False / any-True rules dominate unknowns)."""
    gid = cell.fission["group"]
    n = 0
    for sib in cell.request.cells:
        if sib is cell or sib.fission is None \
                or sib.fission.get("group") != gid:
            continue
        if sib.result is None and not sib.cancelled:
            sib.cancelled = True
            n += 1
    if n:
        _bump(cancelled=n)
        RECORDER.record("fission", "cancel-siblings",
                        trace_id=cell.request.trace_id,
                        span_id=cell.request.span_id,
                        args={"group": gid, "cancelled": n})
