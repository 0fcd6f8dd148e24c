"""The elle_tpu engine: grouping, sharding, budgets, degradation chain.

``check_batch`` fans a set of histories out as lanes of the vmapped
closure kernel:

- lanes are dispatched in bounded groups — at most
  ``parallel.batch.MAX_LANES_PER_GROUP`` (the vmap-width cap that
  module's bool-scatter repro established; the one-hot-matmul kernel
  avoids the scatter, but staying under the proven-safe width costs
  nothing) and at most ``LANE_CELLS_PER_GROUP / n_pad^2`` lanes, so that
  many small lanes share a dispatch and a large one goes alone (a lane
  past the budget is its own group: what it holds on the device is then
  its own size's, see ``LANE_CELLS_PER_GROUP``);
- with a ``mesh``, each group is padded to the lane axis and sharded
  with ``NamedSharding(mesh, P(axis, ...))`` like parallel/batch.py —
  pure SPMD fan-out, no collectives;
- ``budget_s`` bounds the *whole call's* witness recovery: every lane's
  CPU search gets a SearchBudget deadline at the call's remaining time
  (the device pass itself is a handful of bounded matmuls — it's the
  host-side cycle search that can wedge, see elle.graph.SearchBudget);
- a device failure downgrades the affected group to the CPU path with a
  ``fallback``/``fallback-chain`` annotation, mirroring
  checker.linearizable's TPU->CPU chain: a device error says nothing
  about the history and must never decide a verdict.
"""

from __future__ import annotations

import logging
import threading
from functools import lru_cache
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from jepsen_tpu.elle_tpu.anomalies import finish_lane
from jepsen_tpu.elle_tpu.encode import (EncodedHistory, dependencies,
                                        encode_analysis)
from jepsen_tpu.elle_tpu.graphs import pack_group, padded_n
from jepsen_tpu.engine.budget import Deadline
from jepsen_tpu.engine.fallback import (
    annotate_fallback, chain_entry, warn_fallback,
)
from jepsen_tpu.engine.groups import (
    MAX_LANES_PER_GROUP, bounded_group_cap,
)
from jepsen_tpu.engine.ladder import pad_words
from jepsen_tpu.history import History
from jepsen_tpu.obs.recorder import span

log = logging.getLogger(__name__)

#: how many lanes share one dispatch: as many as keep (lanes x n_pad^2)
#: adjacency cells under this, so a group of small lanes stays under a few
#: hundred MB of f32 (three closure masks a lane).  It sizes groups and
#: bounds nothing for a lane that is larger by itself (n_pad over 4,096):
#: that lane goes alone, and holds three [n_pad, n_pad] f32 layers at a
#: time, 1.1 GB at n_pad 9,504 (the one-hot operands are fused into the
#: products, never stored), so one chip's 16 GB end near n_pad 36,000.
LANE_CELLS_PER_GROUP = 1 << 24

ENGINES = ("auto", "tpu", "cpu")

_STATS_LOCK = threading.Lock()


def _zero_stats() -> Dict[str, int]:
    return {"calls": 0, "lanes": 0, "groups": 0, "n_pad": 0, "e_pad": 0,
            "closure_rounds": 0, "closure_rounds_cap": 0, "layer_builds": 0,
            "cyclic_lanes": 0, "recoveries": 0, "fallbacks": 0}


_STATS = _zero_stats()


def elle_stats() -> Dict[str, int]:
    """Sums over every :func:`check_batch` of this process: ``calls``;
    ``lanes`` (histories checked); ``groups`` (device dispatches);
    ``closure_rounds`` (squarings the device ran, counted by the kernel and
    read back with the flags: for each of a group's three closures its
    lanes' most, times its lanes, a mesh's padding lanes too; a group that
    fell back to the host adds none) beside ``closure_rounds_cap`` (what the
    same groups would run at the cap, ``closure.closure_rounds``, every
    squaring: the ratio says how often the closures stop early) and
    ``layer_builds`` (one-hot products run, three a dispatched lane): with
    ``n_pad`` and ``e_pad``, the shapes of the last dispatch, the work the
    device was handed; ``cyclic_lanes`` (lanes the device flagged cyclic);
    ``recoveries`` (lanes whose cycles the host then searched for: those,
    and every lane that had no device flags); ``fallbacks`` (groups a
    device error sent to the host)."""
    with _STATS_LOCK:
        return dict(_STATS)


def reset_elle_stats() -> None:
    with _STATS_LOCK:
        _STATS.update(_zero_stats())


def _count(**sums: int) -> None:
    with _STATS_LOCK:
        for k, v in sums.items():
            _STATS[k] += v


def available() -> bool:
    """True when a JAX backend with at least one device is importable —
    the engine itself is backend-agnostic (the kernel is plain jnp)."""
    try:
        import jax
        return len(jax.devices()) > 0
    except Exception:  # noqa: BLE001 — any init failure means "no"
        return False


def group_cap(n_pad: int) -> int:
    return bounded_group_cap(LANE_CELLS_PER_GROUP, n_pad * n_pad)


def check(history: History, **kw) -> Dict[str, Any]:
    """Single-history convenience wrapper over :func:`check_batch`."""
    return check_batch([history], **kw)[0]


def check_batch(histories: Sequence[History],
                workload: str = "list-append",
                realtime: bool = False,
                consistency_models: Optional[Sequence[str]] = None,
                engine: str = "auto",
                mesh=None,
                axis: str = "data",
                budget_s: Optional[float] = None,
                n_pad_floor: int = 0,
                **workload_kw) -> List[Dict[str, Any]]:
    """Check many histories at once; one elle-shaped result per history.

    ``engine``: ``"auto"``/``"tpu"`` run the device pass (falling back to
    CPU per group on device errors), ``"cpu"`` skips the device and runs
    the full CPU search per lane (still through this code path, so budget
    and artifacts behave identically).  ``n_pad_floor`` pads the shared
    adjacency dimension up to a caller-chosen bucket so successive batches
    of similar histories reuse one compiled closure kernel (the serve
    scheduler's shape-bucketing lever; 0 = tightest)."""
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; known: {ENGINES}")
    if not histories:
        return []
    if consistency_models is None:
        consistency_models = (("strict-serializable",) if realtime
                              else ("serializable",))
    deadline = Deadline.after(budget_s)
    # the half of the host pass the device waits for; the other half (the
    # host anomalies, the graph as an object) follows each group's dispatch
    with span("elle.analyze", lanes=len(histories), workload=workload):
        deps = [dependencies(h, workload, **workload_kw) for h in histories]
    with span("elle.encode", lanes=len(histories)):
        encs = [encode_analysis(d, workload) for d in deps]
    # Floor padding shares the ladder's word rounding with padded_n —
    # one derivation, so the serve elle bucket and a floorless call land
    # on identical rungs.
    n_pad = max(padded_n(encs), pad_words(n_pad_floor))
    cap = group_cap(n_pad)
    use_device = engine != "cpu" and available()
    if engine == "tpu" and not use_device:
        raise RuntimeError("elle_tpu device engine requested but no JAX "
                           "device is available")

    groups = [encs[i:i + cap] for i in range(0, len(encs), cap)]
    gflags: List[Optional[np.ndarray]] = [None] * len(groups)
    gchain: List[Optional[List[Dict[str, Any]]]] = [None] * len(groups)
    if use_device:
        _device_flags_pipelined(groups, n_pad, realtime, mesh, axis,
                                gflags, gchain)
    else:
        for group in groups:
            _finish_analyses(group)

    out: List[Dict[str, Any]] = []
    cyclic = recoveries = 0
    for gi, group in enumerate(groups):
        flags = gflags[gi]
        chain = gchain[gi]
        for j, enc in enumerate(group):
            budget = deadline.search_budget()
            res = finish_lane(enc, flags[j] if flags is not None else None,
                              realtime, consistency_models, budget=budget)
            cyclic += flags is not None and bool(flags[j][0])
            recoveries += flags is None or bool(flags[j][0])
            if chain is not None:
                annotate_fallback(res, "elle-tpu", "elle-cpu", chain[0],
                                  chain)
                res["analyzer"] = "elle-cpu"
            elif flags is None:
                res["analyzer"] = "elle-cpu"
            out.append(res)
    _count(calls=1, lanes=len(encs), cyclic_lanes=cyclic,
           recoveries=recoveries,
           fallbacks=sum(c is not None for c in gchain))
    return out


def _finish_analyses(group: Sequence[EncodedHistory]) -> None:
    with span("elle.anomalies", lanes=len(group)):
        for enc in group:
            enc.finish_analysis()


def _device_flags_pipelined(groups, n_pad: int, realtime: bool, mesh,
                            axis: str, gflags, gchain) -> None:
    """Dispatch every lane group asynchronously with a bounded in-flight
    window and a fused per-group readback.

    Group i+1's ``device_put`` (host→device upload of the packed edge
    tensors) overlaps group i's closure matmuls via JAX async dispatch —
    the host never blocks between dispatches, and spends the time on the
    second half of each dispatched lane's host pass (``finish_analysis``:
    nothing in it bears on what the device was handed).  Each group's
    readback is ONE small vector the kernel computes (the flag sum, then
    the squarings each closure ran); the per-lane ``[b, 4]`` flag array
    transfers only for groups where the sum is nonzero.
    A zero sum means the device proved every lane anomaly-free, so the
    all-False flags are synthesized host-side — same verdicts, O(1)
    device→host traffic on the (dominant) clean path.  All groups share
    the one compiled ``lane_flags_fn(n_pad, realtime)`` executable.

    Failures stay per-group: an exception during dispatch or readback
    degrades that group to the CPU path via ``gchain`` (device trouble
    says nothing about the histories), exactly like the old synchronous
    loop."""
    from collections import deque

    from jepsen_tpu.elle_tpu import closure
    from jepsen_tpu.parallel.megabatch import staging_depth_default

    depth = staging_depth_default()
    inflight: deque = deque()

    def _fail(gi, n, e):
        warn_fallback("elle-tpu", "elle-cpu", e, n_lanes=n)
        gchain[gi] = [chain_entry("elle-tpu", e)]

    def _drain():
        gi, b, b_pad, flags_dev, summ_dev = inflight.popleft()
        try:
            # the host blocked on the chip: the summary is ready when the
            # group's closures are done
            with span("elle.readback", group=gi, lanes=b) as sp:
                summ = np.asarray(summ_dev)
                total = int(summ[0])
                if total == 0:
                    gflags[gi] = np.zeros((b, 4), bool)
                else:
                    gflags[gi] = np.asarray(flags_dev)[:b]
                sp.set(flags_set=total)
        except Exception as e:  # noqa: BLE001 — runtime device trouble
            _fail(gi, b, e)
            return
        _count(closure_rounds=b_pad * int(summ[1:].sum()),
               closure_rounds_cap=b_pad * closure.CLOSURES_PER_LANE
               * closure.closure_rounds(n_pad))

    for gi, group in enumerate(groups):
        try:
            b_pad, flags_dev, summ_dev = _device_flags_async(
                group, n_pad, realtime, mesh, axis)
            inflight.append((gi, len(group), b_pad, flags_dev, summ_dev))
        except Exception as e:  # noqa: BLE001 — dispatch-time trouble
            _fail(gi, len(group), e)
        # the host's own work on this group, while its closures run
        _finish_analyses(group)
        while len(inflight) > depth:
            _drain()
    while inflight:
        _drain()


def _device_flags_async(group: Sequence[EncodedHistory], n_pad: int,
                        realtime: bool, mesh, axis: str):
    """Enqueue one vmapped dispatch over a lane group; returns ``b_pad``
    (the lanes dispatched) and the un-read device ``[b_pad, 4]`` flag
    array and ``[4]`` summary — no host sync happens here (JAX async
    dispatch)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from jepsen_tpu.elle_tpu import closure

    b = len(group)
    b_pad = b
    if mesh is not None:
        n_sh = mesh.shape[axis]
        b_pad = ((b + n_sh - 1) // n_sh) * n_sh
    with span("elle.pack", lanes=b, n_pad=n_pad) as sp:
        packed = pack_group(group, n_pad=n_pad, b_pad=b_pad)
        arrays = {k: jnp.asarray(v) for k, v in packed.items()}
        if mesh is not None:
            arrays = {k: jax.device_put(
                v, NamedSharding(mesh, P(axis, *([None] * (v.ndim - 1)))))
                for k, v in arrays.items()}
        e_pad = packed["src"].shape[2]
        sp.set(e_pad=e_pad, bytes=sum(v.nbytes for v in packed.values()))
    with span("elle.dispatch", lanes=b_pad, n_pad=n_pad, e_pad=e_pad):
        flags, _, summ = _timed_lane_flags(n_pad, realtime)(
            arrays["src"], arrays["dst"],
            arrays["invoke"], arrays["complete"])
    with _STATS_LOCK:
        _STATS["groups"] += 1
        _STATS["n_pad"], _STATS["e_pad"] = n_pad, e_pad
        _STATS["layer_builds"] += b_pad * closure.LAYER_BUILDS_PER_LANE
    return b_pad, flags, summ


@lru_cache(maxsize=None)
def _timed_lane_flags(n_pad: int, realtime: bool):
    """The shape class's kernel with its first call named: what JAX traces,
    lowers and loads under it is that shape's in ``first_use_stats()``."""
    from jepsen_tpu.elle_tpu.closure import lane_flags_fn
    from jepsen_tpu.obs.hist import timed_first_call
    return timed_first_call(lane_flags_fn(n_pad, realtime),
                            f"compile:elle:n{n_pad}:rt{int(realtime)}")
