"""Frontier fission: split the WGL search instead of escalating capacity.

The capacity-escalation ladder (engine.ladder) treats an overflowing
configuration frontier as a *sizing* problem: compile a bigger engine and
re-run.  Past a point that is the wrong physics — per-round sort cost
scales with the static capacity, the 65536 ceiling turns into a hard
``valid: unknown`` wall, and one giant frontier monopolizes the device
while the batch/megabatch lanes it could have become sit idle.  This
module turns the wall into *fission*: when escalation would cross a
configurable threshold, the search splits into sub-problems whose
frontiers fit small, cache-hot bucket shapes, and the sub-verdicts
recombine under the engine substrate's unknown-never-false discipline.

Two splitters, applied in order, and between them the step that needs no
split: a history in one component whose frontier outgrew one chip, on a host
with more chips attached, keeps its one search and divides the frontier
over the chips (``parallel.sharded``, analyzer ``wgl-tpu-sharded``).  The
step chooses from what it observes (the devices attached), with no knob;
on one chip nothing changes.

1. **Component split (P-compositionality, arXiv 1504.00204).**  When the
   model declares per-key independence (``JaxModel.components``), the
   history partitions into sub-histories over connected components of
   touched keys — the Herlihy–Wing locality theorem makes the conjunction
   exact: the history is linearizable iff every projection is, and a
   refuted projection refutes the whole.  This pushes ``serve/decompose``'s
   admission-time per-key projection into the search itself, where it also
   fires on histories that arrived as one cell.

2. **Ghost case-split (decrease-and-conquer, arXiv 2410.04581).**  With no
   independence to exploit, the frontier blowup is almost always the
   2^ghosts ambiguity of crashed ops (each may or may not have taken
   effect).  The split enumerates that ambiguity *outside* the engine: a
   history is linearizable iff for SOME subset S of its ghosts the variant
   "force S (must linearize by stream end), elide the rest (never took
   effect)" is linearizable — an exact disjunction.  Every variant is
   ghost-free, so it runs the lean engine on a small shape; the all-elided
   variant is checked first (a valid verdict short-circuits the whole
   disjunction), and the remaining 2^k - 1 variants dispatch as ordinary
   batch lanes (small ones through megabatch).

Recombination rules (the SOUND01 contract, table form in docs/fission.md):

===============  ==========================================================
sub-verdicts      combined verdict
===============  ==========================================================
components: any False   False — refuting op + witness from that sub-problem only
components: all True    True
components: else        unknown (never false)
one component, more than one chip: shard   the sharded search's own verdict; its overflow is unknown
ghosts: any True        True
ghosts: all False       False — witness from the all-elided sub-problem
ghosts: else            monolithic escalation to the caller's real ceiling
===============  ==========================================================

Knobs (README env table): ``JTPU_FISSION`` (default on),
``JTPU_FISSION_THRESHOLD`` (default 16384 — the last capacity rung reached
before splitting), ``JTPU_FISSION_MAX_SUBPROBLEMS`` (default 256 — caps
the ghost enumeration at 2^8 variants).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from jepsen_tpu.engine import ladder
from jepsen_tpu.history import FAIL, History, INFO, INVOKE, OK, Op
from jepsen_tpu.models.base import JaxModel, UNKNOWN32
from jepsen_tpu.obs.hist import HistogramSet
from jepsen_tpu.obs.recorder import RECORDER, instant

DEFAULT_THRESHOLD = 16384
DEFAULT_MAX_SUBPROBLEMS = 256

ANALYZER = "wgl-tpu-fission"

#: Sub-problem wall-clock histograms, exported with the fission counters
#: in the serve /metrics snapshot (PR 10 observability discipline).
HISTS = HistogramSet()


# ---------------------------------------------------------------------------
# Knobs
# ---------------------------------------------------------------------------

def fission_enabled() -> bool:
    return os.environ.get("JTPU_FISSION", "1").lower() \
        not in ("0", "false", "no", "off", "")


def fission_threshold() -> int:
    """Capacity rung past which the search splits instead of escalating."""
    try:
        return max(1, int(os.environ.get("JTPU_FISSION_THRESHOLD",
                                         DEFAULT_THRESHOLD)))
    except ValueError:
        return DEFAULT_THRESHOLD


def fission_max_subproblems() -> int:
    """Ceiling on ghost-enumeration variants (2^ghosts must fit)."""
    try:
        return max(2, int(os.environ.get("JTPU_FISSION_MAX_SUBPROBLEMS",
                                         DEFAULT_MAX_SUBPROBLEMS)))
    except ValueError:
        return DEFAULT_MAX_SUBPROBLEMS


# ---------------------------------------------------------------------------
# Counters (megabatch_stats idiom; exported in the /metrics snapshot)
# ---------------------------------------------------------------------------

_STATS_LOCK = threading.Lock()


def _zero_stats() -> Dict[str, int]:
    return {"checks": 0, "splits": 0,
            "component_splits": 0, "component_subproblems": 0,
            "ghost_splits": 0, "ghost_subproblems": 0, "shards": 0,
            "recombines": 0, "short_circuits": 0,
            "sub_overflows": 0, "escalations": 0, "errors": 0}


_STATS = _zero_stats()


def fission_stats() -> Dict[str, int]:
    """Counters over every fission decision in this process: splits taken,
    sub-problems spawned per splitter, searches handed to the sharded
    engine whole (``shards``), recombinations, all-elided
    short-circuits, sub-problems that themselves overflowed the threshold,
    and monolithic escalations (the pre-fission behavior, taken only when
    neither splitter can decide)."""
    with _STATS_LOCK:
        return dict(_STATS)


def reset_fission_stats() -> None:
    with _STATS_LOCK:
        _STATS.update(_zero_stats())


def _bump(**kw: int) -> None:
    with _STATS_LOCK:
        for k, v in kw.items():
            _STATS[k] += v


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def check(model: JaxModel, history: Optional[History] = None,
          prepared: Any = None,
          capacity: int = 1024, max_capacity: int = 65536,
          threshold: Optional[int] = None,
          max_subproblems: Optional[int] = None,
          fission: Optional[bool] = None,
          shard_devices: Optional[Sequence[Any]] = None,
          explain: bool = True, **opts: Any) -> Dict[str, Any]:
    """Drop-in for :func:`jepsen_tpu.checker.wgl_tpu.check` with frontier
    fission above the threshold.

    Below the threshold this IS ``wgl_tpu.check`` (same escalation ladder,
    same resume-from-snapshot growth) — callers whose ``max_capacity``
    never crosses the threshold see byte-identical behavior.  Above it,
    the monolithic search runs with its ceiling clamped to the threshold;
    on capacity exhaustion the search splits (see the module docstring)
    instead of compiling ever-larger engines.  ``fission=None`` reads the
    ``JTPU_FISSION`` knob; ``threshold``/``max_subproblems`` default to
    their env knobs.  ``shard_devices`` are the devices a search in one
    component may divide its frontier over; the default is what is
    attached (:func:`attached_chips`), and a test on the CPU's virtual
    devices hands them in.  Remaining kwargs pass through to
    ``wgl_tpu.check``.
    """
    from jepsen_tpu.checker import wgl_tpu
    thr = threshold if threshold is not None else fission_threshold()
    enabled = fission if fission is not None else fission_enabled()
    if not enabled or history is None or max_capacity <= thr:
        return wgl_tpu.check(model, history, prepared=prepared,
                             capacity=capacity,
                             max_capacity=max_capacity, explain=explain,
                             **opts)
    _bump(checks=1)
    snapshot: List[wgl_tpu.Snapshot] = []
    r = wgl_tpu.check(model, history, prepared=prepared,
                      capacity=min(capacity, thr),
                      max_capacity=thr, explain=explain, snapshot=snapshot,
                      **opts)
    if not r.get("capacity-exceeded"):
        return r
    return split_check(model, history, capacity=capacity,
                       max_capacity=max_capacity, threshold=thr,
                       max_subproblems=max_subproblems, explain=explain,
                       base_explored=int(r.get("configs-explored", 0)),
                       shard_devices=shard_devices,
                       resume=snapshot[0] if snapshot else None, **opts)


def split_check(model: JaxModel, history: History,
                capacity: int = 1024, max_capacity: int = 65536,
                threshold: Optional[int] = None,
                max_subproblems: Optional[int] = None,
                explain: bool = True, base_explored: int = 0,
                shard_devices: Optional[Sequence[Any]] = None,
                resume: Any = None, **opts: Any) -> Dict[str, Any]:
    """Split an already-overflowed search into sub-problems and recombine,
    or, where there is one component and more than one chip, go on with
    the one search sharded over the chips (:func:`_shard`), from the
    overflowed search's snapshot (``resume``) where the caller has one.

    Called by :func:`check` after its threshold-clamped monolithic run
    overflowed, and by ``parallel.batch.check_batch`` for lanes whose next
    escalation rung would cross the threshold.  Any internal failure
    degrades to the monolithic escalation path (the exact pre-fission
    behavior), never to a fabricated verdict; a failure of the sharded
    search is the device's and goes up to the caller's fallback chain."""
    thr = threshold if threshold is not None else fission_threshold()
    max_subs = (max_subproblems if max_subproblems is not None
                else fission_max_subproblems())
    _bump(splits=1)
    t0 = time.monotonic()
    devices = list(attached_chips() if shard_devices is None
                   else shard_devices)
    try:
        subs = component_split(model, history)
        whole = subs is None or len(subs) < 2
        if not whole:
            res = _check_components(model, subs, threshold=thr,
                                    max_capacity=max_capacity,
                                    max_subproblems=max_subs,
                                    explain=explain,
                                    base_explored=base_explored, **opts)
        elif len(devices) < 2:
            res = _ghost_split(model, history, capacity=capacity,
                               threshold=thr, max_capacity=max_capacity,
                               max_subproblems=max_subs, explain=explain,
                               base_explored=base_explored, **opts)
    except Exception as e:  # noqa: BLE001 — splitting must never lose a verdict
        _bump(errors=1)
        res = _escalate(model, history, capacity=capacity,
                        max_capacity=max_capacity, explain=explain,
                        why=f"fission error: {type(e).__name__}: {e}",
                        threshold=thr, **opts)
    else:
        if whole and len(devices) >= 2:
            res = _shard(model, history, devices, capacity=capacity,
                         threshold=thr, explain=explain, resume=resume,
                         **opts)
    dt = time.monotonic() - t0
    HISTS.observe("fission:split", dt)
    RECORDER.record("fission", "split", dur_s=dt,
                    args={"verdict": str(res.get("valid")),
                          "mode": (res.get("fission") or {}).get("mode")})
    return res


# ---------------------------------------------------------------------------
# One component, more than one chip: the frontier sharded
# ---------------------------------------------------------------------------

def attached_chips() -> List[Any]:
    """The devices one search may divide its frontier over: every device
    of the default backend where that is an accelerator, none on the CPU
    (its devices are one host's cores under other names, and the virtual
    ones of ``tests/conftest.py`` a test rig)."""
    import jax
    devices = jax.devices()
    return [] if devices[0].platform == "cpu" else list(devices)


def _shard(model: JaxModel, history: History, devices: Sequence[Any], *,
           capacity: int, threshold: int, explain: bool, resume: Any,
           **opts: Any) -> Dict[str, Any]:
    """The search goes on as one, its frontier over ``devices``, each shard
    on the ladder the one chip climbed, up to ``threshold`` rows a shard.
    With ``resume`` (``fission.check`` has the overflowed search's
    snapshot) it goes on from the chunk that overflowed, at the first rung
    whose shards together hold twice the peak; without (a lane of
    ``check_batch``) from event 0 and the first rung, which repeats the
    events the one chip had consumed and compiles the small rungs' sharded
    programs besides.  The verdict is the sharded engine's own: past its
    ceiling ``unknown``, and no split or one-chip escalation after it,
    which could only meet the same frontier at the same global capacity."""
    from jepsen_tpu.parallel.sharded import check_sharded
    _bump(shards=1)
    instant("drivers.shard_handover",
            event=resume.cursor if resume is not None else 0,
            peak=resume.peak if resume is not None else 0,
            mode="resume" if resume is not None else "restart")
    res = check_sharded(model, history, devices=devices,
                        capacity_per_shard=min(capacity, threshold),
                        max_capacity_per_shard=threshold, resume=resume,
                        explain=explain, **opts)
    res["fission"] = {"mode": "shard", "shards": len(devices)}
    return res


# ---------------------------------------------------------------------------
# Component split (P-compositionality)
# ---------------------------------------------------------------------------

def component_split(model: JaxModel,
                    history: History) -> Optional[List[History]]:
    """Partition a history into independent per-component sub-histories,
    or None when the model declares no independence / any op spans the
    whole object / everything lands in one component.

    Components are connected components of the "shares a key" relation
    over the model's ``components`` hook (union-find).  Each invoke and
    its completion travel together; ``fail`` pairs are dropped (they never
    took effect — prep.py removes them anyway), and unconstraining ops
    (hook returns an empty set) are elided: they are always linearizable
    and state-preserving, so they decide nothing in any component."""
    comp = getattr(model, "components", None)
    if comp is None:
        return None
    h = history.client_ops().complete()
    pairs = h.pair_index()

    parent: Dict[Any, Any] = {}

    def find(k):
        root = k
        while parent.get(root, root) != root:
            root = parent[root]
        while parent.get(k, k) != k:
            parent[k], k = root, parent[k]
        return root

    def union(a, b):
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[rb] = ra

    key_of: Dict[int, Any] = {}  # invoke position -> one of its keys
    for i, op in enumerate(h.ops):
        if op.type != INVOKE:
            continue
        j = int(pairs[i])
        ctype = h.ops[j].type if j >= 0 else INFO
        if ctype == FAIL:
            continue
        keys = comp(op)
        if keys is None:
            return None
        ks = sorted(keys, key=repr)
        if not ks:
            continue
        parent.setdefault(ks[0], ks[0])
        for k in ks[1:]:
            parent.setdefault(k, k)
            union(ks[0], k)
        key_of[i] = ks[0]

    groups: Dict[Any, List[int]] = {}
    order: List[Any] = []
    for i in sorted(key_of):
        root = find(key_of[i])
        if root not in groups:
            groups[root] = []
            order.append(root)
        groups[root].append(i)
        j = int(pairs[i])
        if j >= 0:
            groups[root].append(j)
    if len(order) < 2:
        return None
    return [History([h.ops[p] for p in sorted(groups[root])], reindex=True)
            for root in order]


def _check_components(model: JaxModel, subs: List[History], *,
                      threshold: int, max_capacity: int,
                      max_subproblems: int, explain: bool,
                      base_explored: int, **opts: Any) -> Dict[str, Any]:
    _bump(component_splits=1, component_subproblems=len(subs))
    RECORDER.record("fission", "component-split",
                    args={"subproblems": len(subs)})
    results = _dispatch_subproblems(model, subs, threshold=threshold)
    # A component can itself be too entangled for the threshold (e.g. all
    # its ghosts share one key): resolve each such lane with the ghost
    # case-split before recombining — components are already maximal, so
    # re-splitting by key cannot help.
    for i, r in enumerate(results):
        if r.get("valid") not in (True, False) and _exceeded(r):
            _bump(sub_overflows=1)
            results[i] = _ghost_split(
                model, subs[i], capacity=min(256, threshold),
                threshold=threshold, max_capacity=max_capacity,
                max_subproblems=max_subproblems, explain=explain,
                base_explored=0, **opts)
    return _recombine_components(model, subs, results, explain=explain,
                                 base_explored=base_explored)


def _recombine_components(model: JaxModel, subs: List[History],
                          results: List[Dict[str, Any]], *, explain: bool,
                          base_explored: int) -> Dict[str, Any]:
    _bump(recombines=1)
    explored = base_explored + sum(
        int(r.get("configs-explored", 0) or 0) for r in results)
    meta = {"mode": "components", "subproblems": len(subs)}
    for h, r in zip(subs, results):
        if r.get("valid") is False:
            # Locality: a refuted independent projection refutes the whole
            # history; the witness is re-derived on that sub-problem only.
            # witness: refuting op from the refuted sub-problem attached; CPU witness on that sub-history
            out = {"valid": False, "analyzer": ANALYZER,
                   "op": r.get("op"), "configs-explored": explored,
                   "fission": {**meta, "refuting-subproblem": True}}
            if "witness" in r:
                out["witness"] = r["witness"]
            elif explain and r.get("op") and model.cpu_model is not None:
                from jepsen_tpu.engine.witness import cpu_witness
                out["witness"] = cpu_witness(model, h,
                                             Op.from_dict(r["op"]))
            return out
    if all(r.get("valid") is True for r in results):
        return {"valid": True, "analyzer": ANALYZER,
                "configs-explored": explored, "fission": meta}
    errs = [r.get("error") for r in results
            if r.get("valid") not in (True, False)]
    return {"valid": "unknown", "analyzer": ANALYZER,
            "error": f"{len(errs)} fission sub-problem(s) indefinite: "
                     f"{errs[0]}",
            "configs-explored": explored, "fission": meta}


# ---------------------------------------------------------------------------
# Ghost case-split (decrease-and-conquer)
# ---------------------------------------------------------------------------

def _real_ghosts(model: JaxModel,
                 h: History) -> Optional[List[Tuple[int, int]]]:
    """Positions of (invoke, info-completion-or--1) pairs that actually
    constrain the search, in ``h`` (client ops, uncompleted).  Mirrors
    prep.py's elimination: a crashed pure read with an unknown operand
    never enters the pending window, so forcing it could only fabricate
    constraints — it is left in place for prepare to drop.  Returns None
    when the model cannot encode an op (fission then escalates)."""
    pairs = h.pair_index()
    pure_fs = set(model.pure_read_fs)
    ghosts: List[Tuple[int, int]] = []
    for i, op in enumerate(h.ops):
        if op.type != INVOKE:
            continue
        j = int(pairs[i])
        ctype = h.ops[j].type if j >= 0 else INFO
        if ctype != INFO:
            continue
        try:
            f, a, _b = model.encode_op(op)
        except Exception:  # noqa: BLE001 — undecodable op: leave history alone
            return None
        if pure_fs and f in pure_fs and a == UNKNOWN32:
            continue
        ghosts.append((i, j))
    return ghosts


def _fresh_process_base(h: History) -> int:
    return max((op.process for op in h.ops
                if isinstance(op.process, int)), default=0) + 1


def ghost_variant(h: History, ghosts: Sequence[Tuple[int, int]],
                  force_mask: int) -> History:
    """The ghost-free variant of ``h`` for one subset of its ghosts.

    Ghosts whose bit is clear in ``force_mask`` are *elided* (invoke and
    info completion dropped: the op never took effect); set bits are
    *forced*: the invoke stays in place under a fresh process id (process
    ids are reused after crashes — keeping the original would mis-pair
    with a later op of the same process once the info completion is gone)
    and an ok completion carrying the invoke's value is appended at stream
    end, i.e. "took effect somewhere between invocation and the end" —
    exactly the engines' ghost-linearization window."""
    fresh = _fresh_process_base(h)
    drop = set()
    forced: Dict[int, int] = {}
    for gi, (i, j) in enumerate(ghosts):
        if (force_mask >> gi) & 1:
            forced[i] = fresh + gi
            if j >= 0:
                drop.add(j)
        else:
            drop.add(i)
            if j >= 0:
                drop.add(j)
    out: List[Op] = []
    tail: List[Op] = []
    for pos, op in enumerate(h.ops):
        if pos in drop:
            continue
        if pos in forced:
            p = forced[pos]
            out.append(op.with_(process=p))
            tail.append(Op(process=p, type=OK, f=op.f, value=op.value))
        else:
            out.append(op)
    return History(out + tail, reindex=True)


def _ghost_split(model: JaxModel, history: History, *, capacity: int,
                 threshold: int, max_capacity: int, max_subproblems: int,
                 explain: bool, base_explored: int,
                 **opts: Any) -> Dict[str, Any]:
    from jepsen_tpu.checker import wgl_tpu
    h = history.client_ops()
    ghosts = _real_ghosts(model, h)
    if ghosts is None or not ghosts:
        return _escalate(model, history, capacity=capacity,
                         max_capacity=max_capacity, explain=explain,
                         why="no ghosts to split on",
                         threshold=threshold, **opts)
    k = len(ghosts)
    if (1 << k) > max_subproblems:
        return _escalate(model, history, capacity=capacity,
                         max_capacity=max_capacity, explain=explain,
                         why=f"2^{k} ghost variants exceed the "
                             f"{max_subproblems} sub-problem cap",
                         threshold=threshold, **opts)
    _bump(ghost_splits=1, ghost_subproblems=1 << k)
    RECORDER.record("fission", "ghost-split",
                    args={"ghosts": k, "variants": 1 << k})
    meta = {"mode": "ghosts", "ghosts": k, "subproblems": 1 << k}
    # The all-elided variant first: "no crashed op took effect" is the
    # common case, and a valid verdict short-circuits the disjunction.
    elided = ghost_variant(h, ghosts, 0)
    r0 = wgl_tpu.check(model, elided, capacity=min(capacity, threshold),
                       max_capacity=threshold, explain=explain, **opts)
    explored = base_explored + int(r0.get("configs-explored", 0) or 0)
    if r0.get("valid") is True:
        _bump(short_circuits=1, recombines=1)
        return {"valid": True, "analyzer": ANALYZER,
                "configs-explored": explored,
                "fission": {**meta, "short-circuit": True}}
    variants = [ghost_variant(h, ghosts, m) for m in range(1, 1 << k)]
    results = _dispatch_subproblems(model, variants, threshold=threshold)
    _bump(recombines=1)
    explored += sum(int(r.get("configs-explored", 0) or 0)
                    for r in results)
    for r in results:
        if r.get("valid") is True:
            return {"valid": True, "analyzer": ANALYZER,
                    "configs-explored": explored, "fission": meta}
    if r0.get("valid") is False and \
            all(r.get("valid") is False for r in results):
        # Every branch of the exact disjunction is refuted, so the history
        # is not linearizable under ANY crashed-op outcome.  The canonical
        # evidence is the all-elided branch's refutation (its witness was
        # re-derived on that sub-problem only).
        # witness: all 2^ghosts case-split branches refuted; all-elided branch's refuting op + witness attached
        out = {"valid": False, "analyzer": ANALYZER, "op": r0.get("op"),
               "configs-explored": explored, "fission": meta}
        if "witness" in r0:
            out["witness"] = r0["witness"]
        return out
    # Indefinite branches and no valid one: the disjunction cannot
    # conclude — fall back to the pre-fission behavior (escalate the
    # monolithic search to the caller's real ceiling; unknown, never
    # false, if that overflows too).
    return _escalate(model, history, capacity=capacity,
                     max_capacity=max_capacity, explain=explain,
                     why="ghost case-split indefinite",
                     threshold=threshold, **opts)


# ---------------------------------------------------------------------------
# Sub-problem dispatch + escalation
# ---------------------------------------------------------------------------

def _exceeded(r: Dict[str, Any]) -> bool:
    return bool(r.get("capacity-exceeded")) \
        or "capacity exceeded" in str(r.get("error", ""))


def subproblem_floors(subs: Sequence[History]) -> Tuple[int, int]:
    """The shared (window, events) bucket floors for one sub-problem
    dispatch — every lane rides the same compiled shape, and both floors
    are ladder images (never raw history shapes): the TRACE02 seam the
    trace lint runs the real derivation through."""
    return (max(ladder.width_bucket(h) for h in subs),
            max(ladder.events_bucket(h) for h in subs))


def _dispatch_subproblems(model: JaxModel, subs: Sequence[History], *,
                          threshold: int) -> List[Dict[str, Any]]:
    """Run sub-problems as ordinary engine-substrate lanes.

    Shapes are bucket-derived (SHAPE01): one shared window/events floor
    over the sub-problems keeps every dispatch on the ladder.  Small
    sub-problem swarms route through megabatch (continuous refill eats
    hundreds of tiny lanes); the rest run as plain batch lanes.  Both run
    with fission pinned OFF and the threshold as their capacity ceiling,
    so a sub-problem can never re-split or out-escalate its parent."""
    t0 = time.monotonic()
    w_floor, ev_floor = subproblem_floors(subs)
    from jepsen_tpu.parallel.megabatch import megabatch_enabled
    if len(subs) >= 4 and megabatch_enabled() \
            and ev_floor <= ladder.MEGA_EVENTS_MAX:
        from jepsen_tpu.parallel.megabatch import check_megabatch
        out = check_megabatch(model, list(subs), max_capacity=threshold,
                              window_floor=w_floor, ev_floor=ev_floor,
                              lanes=ladder.mega_lane_bucket(len(subs)))
    else:
        from jepsen_tpu.parallel.batch import check_batch
        out = check_batch(model, list(subs),
                          capacity=min(256, threshold),
                          max_capacity=threshold,
                          window_floor=w_floor, fission=False)
    dt = time.monotonic() - t0
    HISTS.observe("fission:subdispatch", dt)
    RECORDER.record("fission", "subdispatch", dur_s=dt,
                    args={"lanes": len(subs), "ev_floor": ev_floor,
                          "w_floor": w_floor})
    return out


def _escalate(model: JaxModel, history: History, *, capacity: int,
              max_capacity: int, explain: bool, why: str,
              threshold: Optional[int] = None,
              **opts: Any) -> Dict[str, Any]:
    """The pre-fission behavior: escalate the monolithic frontier to the
    caller's real ceiling.  Taken only when neither splitter applies or
    the split could not decide — fission never returns a worse verdict
    than the escalation ladder would have.  When even the real ceiling
    overflows and a ``threshold`` is known, the window-shrinking recursion
    (engine.shrink, arXiv 2410.04581) gets one last shot at a refutation
    on threshold-sized prefixes; its False-or-unknown envelope means this
    can only improve the verdict, never change a concluded one."""
    from jepsen_tpu.checker import wgl_tpu
    _bump(escalations=1)
    RECORDER.record("fission", "escalate", args={"why": why})
    t0 = time.monotonic()
    res = wgl_tpu.check(model, history, capacity=capacity,
                        max_capacity=max_capacity, explain=explain, **opts)
    HISTS.observe("fission:escalate", time.monotonic() - t0)
    res.setdefault("fission", {"mode": "escalate", "why": why})
    if threshold is not None and res.get("valid") not in (True, False) \
            and (res.get("capacity-exceeded")
                 or "capacity exceeded" in str(res.get("error", ""))):
        from jepsen_tpu.engine import shrink
        if shrink.shrink_enabled():
            sres = shrink.shrink_check(model, history, threshold=threshold,
                                       capacity=min(capacity, threshold),
                                       explain=explain, **opts)
            if sres.get("valid") is False:
                # witness: shrink refutation carries the refuting prefix's op + witness (engine.shrink soundness)
                sres["fission"]["escalate-why"] = why
                return sres
    return res
