"""Batch-parallel checking: many independent histories, sharded over a mesh.

This is the device-side realization of the reference's per-key parallel
checking (jepsen.independent/checker splits a multi-key history and runs
sub-checkers in a bounded pmap, jepsen/src/jepsen/independent.clj:266-317):
sub-histories become lanes of a vmapped engine, and lanes are sharded across
the ``data`` mesh axis with pjit — no collectives needed, pure SPMD fan-out.

**Watchdog bounding (round-4).**  Under vmap, ``lax.cond``/``switch``
execute EVERY branch for the whole batch, so the standard engine's
fixpoint loops and multi-width merges multiply into per-step costs that
outrun the TPU worker's ~60 s watchdog (the round-2/3 batch-tier killer).
The batched engine therefore runs in *single-round* mode
(``make_engine(single_round_closure=True)``): exactly one fixed-width
merge per scan step, a pending-return register continuing multi-round
closures across steps, and each lane's step gathering its next event by
the lane's own absolute ``consumed`` cursor — per-step device work is a
constant, a dispatch's wall-clock is bounded by its step count alone,
and lanes progress at fully independent rates with no idle steps.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jepsen_tpu.checker.prep import PreparedHistory, prepare
from jepsen_tpu.checker.wgl_tpu import EV_NOP, events_array, make_engine
from jepsen_tpu.engine.budget import exhausted_result
from jepsen_tpu.engine.cache import CACHE as _CACHE
from jepsen_tpu.engine.groups import MAX_LANES_PER_GROUP, group_slices
from jepsen_tpu.engine.ladder import batch_shape, mega_chunk, next_capacity
from jepsen_tpu.engine.witness import refuted_result
from jepsen_tpu.history import History
from jepsen_tpu.models.base import JaxModel
from jepsen_tpu.obs.recorder import instant, span

# ---------------------------------------------------------------------------
# Counters (fission_stats idiom): what the lanes asked for against what the
# batch shape made the device span
# ---------------------------------------------------------------------------

_STATS_LOCK = threading.Lock()
_STATS = {"events_useful": 0, "events_dispatched": 0}


def batch_stats() -> Dict[str, int]:
    """Sums over every ``_run_lanes`` pass of this process, in events, so
    that closure rounds (steps that hold a lane's cursor) count on neither
    side: ``events_dispatched``, the event slots the passes spanned (padded
    lanes x the furthest cursor: every lane rides until the last one
    stops), and ``events_useful``, those of them that held an event of a
    lane the pass answered.  The rest is what one shared shape costs
    (lane padding, lanes shorter than the longest) and what a restart from
    event 0 costs (an overflowed lane's events count for nothing here and
    are spanned again at the next rung)."""
    with _STATS_LOCK:
        return dict(_STATS)


def donate_carry_argnums() -> tuple:
    """Argnums to donate for the per-chunk engine carry.

    The carry is the dominant device allocation (capacity x window words
    per lane); donating it lets XLA update it in place instead of
    reallocating every dispatch.  The CPU backend cannot honor carry
    donation (it warns per call and copies anyway), so donation is gated
    on the real backend — shapes and results are identical either way.
    """
    try:
        return (0,) if jax.default_backend() != "cpu" else ()
    except Exception:  # backend probe must never break checking
        return ()


def check_batch(model: JaxModel,
                histories: Sequence[History],
                mesh: Optional[Mesh] = None,
                axis: str = "data",
                capacity: int = 256,
                max_capacity: int = 65536,
                chunk: Optional[int] = None,
                window_floor: int = 0,
                fission: Optional[bool] = None,
                _group_reuse: bool = False) -> List[Dict[str, Any]]:
    """Check many histories at once; returns one result dict per history.

    All lanes share one engine shape (window = max over histories, events
    NOP-padded to the longest).  With ``mesh``, lanes are sharded over the
    ``axis`` mesh axis; the batch is padded to a multiple of the axis size.
    ``chunk=None`` picks the batch-size-scaled default (``ladder.mega_chunk``).
    ``window_floor`` pads the shared window up to a caller-chosen bucket so
    successive batches of similar histories reuse one compiled engine (the
    serve scheduler's shape-bucketing lever; 0 = tightest window).

    Unlike the single-history engine (kernel-latency bound, per-round
    cost flat in capacity), the vmapped engine's per-step cost IS
    capacity-proportional — every lane pays C+NC merge rows every step —
    so the default capacity starts LOW (measured on hardware: 42 vs 17
    histories/sec at 256 vs 1024 on 200-op crash lanes) and the retry
    loop escalates only the lanes that overflow.

    ``fission`` controls frontier fission for overflowing lanes: once the
    next escalation rung would cross the fission threshold (and the
    caller's ``max_capacity`` lies beyond it), the lane is split into
    independent sub-problems instead of compiling an ever-larger batched
    engine (see :mod:`jepsen_tpu.engine.fission`).  ``None`` reads the
    ``JTPU_FISSION`` knob; fission's own sub-dispatches pin it False so a
    sub-problem can never re-split.
    """
    if not histories:
        return []
    if len(histories) > MAX_LANES_PER_GROUP:
        # Dispatch in bounded groups (engine.groups owns the cap and its
        # bool-scatter/throughput-knee rationale).  Groups share the
        # compiled engine when their shapes agree (the engine cache keys
        # on window/capacity/chunk/bpad).
        out: List[Dict[str, Any]] = []
        for start, stop, reuse in group_slices(len(histories)):
            out.extend(check_batch(model, histories[start:stop],
                                   mesh=mesh, axis=axis, capacity=capacity,
                                   max_capacity=max_capacity, chunk=chunk,
                                   window_floor=window_floor,
                                   fission=fission,
                                   _group_reuse=_group_reuse or reuse))
        return out
    with span("drivers.check_batch", lanes=len(histories)):
        preps = [prepare(h, model) for h in histories]
        window, gw, longest = batch_shape(preps, window_floor=window_floor)
        out: List[Optional[Dict[str, Any]]] = [None] * len(preps)
        lanes = list(range(len(preps)))
        cap: Optional[int] = capacity
        while lanes:
            res = _run_lanes(model, [preps[i] for i in lanes],
                             window, cap, mesh, axis, chunk, gw, longest,
                             group_reuse=_group_reuse)
            retry = []
            for lane, r in zip(lanes, res):
                if r is None:
                    retry.append(lane)
                else:
                    out[lane] = r
            if not retry:
                break
            nxt = next_capacity(cap, max_capacity)
            if _fission_here(fission, nxt, max_capacity):
                # Frontier fission: the remaining lanes' next rung would
                # cross the threshold — split each into sub-problems on
                # small, cache-hot shapes instead of escalating the whole
                # batched engine (unknown-never-false recombination; the
                # monolithic escalation path survives inside fission as
                # the fallback).
                from jepsen_tpu.engine.fission import split_check
                for lane in retry:
                    out[lane] = split_check(model, histories[lane],
                                            capacity=capacity,
                                            max_capacity=max_capacity)
                break
            if nxt is None:
                for lane in retry:
                    out[lane] = exhausted_result(
                        "wgl-tpu-batch", f"capacity exceeded at {cap}",
                        **{"capacity-exceeded": True})
                break
            for lane in retry:
                instant("drivers.lane_retry", lane=lane, cap_from=cap,
                        cap_to=nxt)
            lanes = retry
            cap = nxt
        return out  # type: ignore[return-value]


def _fission_here(fission: Optional[bool], nxt: Optional[int],
                  max_capacity: int) -> bool:
    """Should the escalation loop split instead of taking rung ``nxt``?"""
    from jepsen_tpu.engine.fission import fission_enabled, fission_threshold
    enabled = fission if fission is not None else fission_enabled()
    if not enabled:
        return False
    thr = fission_threshold()
    return max_capacity > thr and (nxt is None or nxt > thr)


def _run_lanes(model: JaxModel, preps, window: int, cap: int,
               mesh: Optional[Mesh], axis: str, chunk: Optional[int],
               gwords: int, longest: int,
               group_reuse: bool = False) -> List[Optional[Dict[str, Any]]]:
    """One vmapped pass over a set of lanes at a fixed capacity.  Returns a
    result per lane, or None where the lane overflowed (caller escalates).

    Each dispatch runs a fixed number of single-round steps; a lane's step
    gathers the event at the lane's own absolute ``consumed`` cursor, so
    lanes progress at fully independent rates and the host just re-invokes
    until every lane's cursor passes its stream (or fails/overflows)."""
    with span("drivers.run_lanes", lanes=len(preps), cap=cap) as sp:
        b = len(preps)
        bpad = b
        if mesh is not None:
            n = mesh.shape[axis]
            bpad = ((b + n - 1) // n) * n
        # The state-width-aware chunk derivation shared with megabatch: one
        # ladder, one bounded (lane, events, state-width)-bucket chunk
        # universe for both dispatch paths.
        cc = chunk if chunk else mega_chunk(bpad, longest, model.state_size)
        with span("drivers.stage") as stage:
            evs = [events_array(p, cc) for p in preps]
            # >= 1 trailing NOP row per lane: finished lanes' cursors clamp
            # onto it (the gather-based engine reads events by each lane's
            # absolute consumed cursor; see wgl_tpu run_chunk's single-round
            # variant).
            emax = max(e.shape[0] for e in evs) + 1
            batch = np.zeros((bpad, emax, 10), np.int32)
            batch[:, :, 0] = EV_NOP
            for i, e in enumerate(evs):
                batch[i, :e.shape[0]] = e
            batch_dev = jnp.asarray(batch)
            if mesh is not None:
                batch_dev = jax.device_put(
                    batch_dev, NamedSharding(mesh, P(axis, None, None)))
            stage.set(bytes=batch.nbytes)

        carry0, vrun = _batched_runner(model, window, cap, gwords, cc, bpad,
                                       group_reuse=group_reuse)
        c0 = carry0()
        carry = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (bpad,) + x.shape), c0)
        if mesh is not None:
            carry = jax.tree.map(
                lambda x: jax.device_put(x, NamedSharding(
                    mesh, P(axis, *([None] * (x.ndim - 1))))),
                carry)

        lane_len = np.array([e.shape[0] for e in evs]
                            + [0] * (bpad - b), np.int32)
        failed = np.zeros(bpad, bool)
        overflow = np.zeros(bpad, bool)
        dispatches = 0
        while True:
            with span("drivers.dispatch"):
                carry, flags = vrun(carry, batch_dev)
            dispatches += 1
            with span("drivers.poll"):
                fl = np.asarray(flags)          # [bpad, 5]
            failed = fl[:, 0].astype(bool)
            overflow = fl[:, 1].astype(bool)
            consumed = fl[:, 3]                 # absolute per-lane cursors
            stalled = fl[:, 4].astype(bool)     # unconverged pending return
            # A lane whose cursor passed its stream may STILL have its final
            # return's closure in flight (consume-on-arrival): it stays live
            # until the stalled flag clears, or its prune could be dropped —
            # a false "valid" on a refuting final return.
            if not (~failed & ~overflow
                    & ((consumed < lane_len) | stalled)).any():
                break
        # a lane's own events behind its cursor (the chunk's NOP tail is none)
        done = np.minimum(consumed[:b], [len(p) for p in preps])
        counts = {"events_useful": int(done[~overflow[:b]].sum()),
                  "events_dispatched": bpad * int(done.max())}
        sp.set(dispatches=dispatches, **counts)
        with _STATS_LOCK:
            for k, v in counts.items():
                _STATS[k] += v

        failed_op = np.asarray(carry[7])[:b]
        explored = np.asarray(carry[9])[:b]
        out: List[Optional[Dict[str, Any]]] = []
        for i in range(b):
            if overflow[i]:
                out.append(None)
            elif failed[i]:
                out.append(refuted_result("wgl-tpu-batch",
                                          preps[i].ops[int(failed_op[i])],
                                          int(explored[i])))
            else:
                out.append({"valid": True, "analyzer": "wgl-tpu-batch",
                            "configs-explored": int(explored[i])})
        return out


def _batched_runner(model: JaxModel, window: int, capacity: int,
                    gwords: int, chunk: int, bpad: int,
                    group_reuse: bool = False):
    key = ("batchv", model.name, model.variant, model.state_size,
           tuple(model.init_state_array().tolist()), window, capacity,
           gwords, chunk, bpad)
    hit = _CACHE.get(key, group_reuse=group_reuse)
    if hit is not None:
        return hit
    # single_round_closure: under vmap every cond/switch branch executes
    # for the whole batch, so the batched engine runs exactly ONE closure
    # round (one fixed-width merge) per scan step — per-step device work
    # is constant, a dispatch's wall-clock is bounded by the step count
    # alone, and no iteration budget is needed (work_budget=0).  Each
    # lane's step gathers its next event by the lane's own absolute
    # consumed cursor, so lanes progress at fully independent rates with
    # no idle steps.
    carry0, _, run_chunk = make_engine(model, window, capacity,
                                       gwords=gwords, work_budget=0,
                                       single_round_closure=True,
                                       steps_per_dispatch=chunk)
    # Donate the carry (argnum 0): the batched carry dominates device
    # memory and is dead after each dispatch — in-place update instead of
    # a fresh allocation per chunk.  The events buffer (argnum 1) is NOT
    # donated; it is reused across every dispatch of the batch.
    vrun = jax.jit(jax.vmap(run_chunk, in_axes=(0, 0)),
                   donate_argnums=donate_carry_argnums())
    from jepsen_tpu.obs.hist import timed_first_call
    vrun = timed_first_call(
        vrun, f"compile:batchv:{model.name}:w{window}:c{capacity}"
              f":k{chunk}:b{bpad}")
    return _CACHE.put(key, (carry0, vrun))
