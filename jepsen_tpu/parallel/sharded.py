"""Frontier sharding: one history's configuration set split across a mesh.

The long-history analog of sequence parallelism (SURVEY.md §5.7): instead of
splitting a history into short per-key pieces the way the reference must
(jepsen/src/jepsen/independent.clj:1-7), the configuration frontier itself is
sharded over the ``model`` mesh axis.  Each device expands its local shard of
configurations (vmapped model steps), candidates are exchanged with
all_gather over ICI, every device deduplicates the global set identically
(replicated sort), and keeps its deterministic slice.  Failure/overflow flags
are psum-reduced so all shards agree.

The host driver mirrors the single-chip lessons (wgl_tpu.check): LOOKAHEAD
chunks stay in flight so the per-chunk flags transfer overlaps device
compute (each chunk-boundary poll is a device→host round trip), an
overflow resumes from the pre-chunk snapshot at a peak-informed capacity
instead of restarting the whole history, and the engine drops back to a
cheaper per-round shape once a crash-burst's transient demand passes.
"""

from __future__ import annotations

from collections import deque
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map as _shard_map

from jepsen_tpu.checker.prep import PreparedHistory, prepare
from jepsen_tpu.checker.wgl_tpu import (EV_NOP, LOOKAHEAD, _chunk_slicer,
                                        chosen_gwords, events_array,
                                        make_engine)
from jepsen_tpu.history import History
from jepsen_tpu.models.base import JaxModel

_CACHE: Dict[Any, Any] = {}


def _sharded_runner(model: JaxModel, window: int, capacity_per_shard: int,
                    mesh: Mesh, axis: str, gwords: int = 1,
                    work_budget: Optional[int] = None):
    key = ("shard", model.name, model.variant, model.state_size,
           tuple(model.init_state_array().tolist()), window,
           capacity_per_shard, id(mesh), axis, gwords, work_budget)
    if key in _CACHE:
        return _CACHE[key]
    n = mesh.shape[axis]
    # The capacity-scaled per-dispatch closure budget (the single-chip
    # watchdog mitigation, wgl_tpu.closure_budget) applies to the sharded
    # engine too; the host loop below resumes mid-chunk from the
    # consumed-events flag exactly like wgl_tpu.check.  Each shard's
    # closure round sorts the *gathered global* set, so the per-iteration
    # cost scales with capacity_per_shard * n — the budget divides by the
    # global capacity, keeping one dispatch's wall-clock at the same bound
    # regardless of shard count.
    if work_budget is None:
        from jepsen_tpu.checker.wgl_tpu import closure_budget
        work_budget = closure_budget(capacity_per_shard * n)
    _, _, run_chunk = make_engine(model, window, capacity_per_shard,
                                  axis_name=axis, num_shards=n,
                                  gwords=gwords, work_budget=work_budget)
    # carry layout: (mask[C,MW], states[C,S], valid[C], win_ops, active,
    #               dirty, failed, failed_op, overflow, explored, rounds,
    #               peak, ghosts, budget, consumed, cl_iters, fresh[W],
    #               cur_new[C]) — ghosts/fresh are per-slot and the
    #               scalars are identical across shards, hence replicated;
    #               cur_new is a per-row delta flag, sharded like valid.
    sharded = P(axis)
    repl = P()
    in_specs = ((sharded, sharded, sharded) + (repl,) * 14 + (sharded,),
                repl)
    out_specs = ((sharded, sharded, sharded) + (repl,) * 14 + (sharded,),
                 repl)
    # Replication checking off (check_vma): closure dedup sorts the
    # *gathered* global row set, so every shard computes bit-identical
    # "replicated" scalars (counts, flags), but the varying-axes checker
    # can't prove that post-all_gather.
    fn = jax.jit(_shard_map(run_chunk, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False))
    _CACHE[key] = fn
    return fn


def _initial_carry(model, window, cap, n, mesh, axis):
    MW = (window + 31) // 32
    gcap = cap * n

    def put(x, spec):
        return jax.device_put(jnp.asarray(x), NamedSharding(mesh, spec))

    return (
        put(np.zeros((gcap, MW), np.uint32), P(axis)),
        put(np.tile(model.init_state_array()[None], (gcap, 1)), P(axis)),
        put(np.arange(gcap) == 0, P(axis)),
        put(np.concatenate([np.zeros((window, 3), np.int32),
                            np.full((window, 1), -1, np.int32),
                            np.zeros((window, 2), np.int32)], axis=1), P()),
        put(np.zeros(window, bool), P()),
        put(np.bool_(False), P()),
        put(np.bool_(False), P()),
        put(np.int32(-1), P()),
        put(np.bool_(False), P()),
        put(np.int32(0), P()),
        put(np.int32(0), P()),
        put(np.int32(1), P()),
        put(np.zeros(MW, np.uint32), P()),
        put(np.int32(0), P()),           # budget (run_chunk resets it)
        put(np.int32(0), P()),           # consumed
        put(np.int32(0), P()),           # cl_iters (paused-closure its)
        put(np.zeros(window, bool), P()),     # fresh slots
        put(np.zeros(gcap, bool), P(axis)),   # cur_new delta frontier
    )


def _resize_carry_sharded(carry, n, old_cap, new_cap, mesh, axis):
    """Re-lay a chunk-boundary carry for a different per-shard capacity.

    Shard i's rows live at global slice [i*cap, (i+1)*cap): a plain global
    pad/truncate would migrate rows across shards, so resize per-shard —
    grow pads each shard's block with dead rows; shrink compacts the global
    live set and deals it round-robin so shards stay balanced for the next
    closure's all_gather.  Host-side: resizes are rare (one per escalation
    step / burst decay), and the buffers are MBs."""
    mask = np.asarray(carry[0]).reshape(n, old_cap, -1)
    states = np.asarray(carry[1]).reshape(n, old_cap, -1)
    valid = np.asarray(carry[2]).reshape(n, old_cap)
    cur_new = np.asarray(carry[17]).reshape(n, old_cap)

    nm = np.zeros((n, new_cap, mask.shape[2]), mask.dtype)
    ns = np.zeros((n, new_cap, states.shape[2]), states.dtype)
    nv = np.zeros((n, new_cap), bool)
    nn = np.zeros((n, new_cap), bool)
    if new_cap >= old_cap:
        nm[:, :old_cap] = mask
        ns[:, :old_cap] = states
        nv[:, :old_cap] = valid
        nn[:, :old_cap] = cur_new
    else:
        # round-robin deal: global live row j -> shard j % n, slot j // n
        idx, sh = np.divmod(np.arange(n * new_cap), n)
        live = np.flatnonzero(valid.reshape(-1))[:n * new_cap]
        k = len(live)
        fm, fs = mask.reshape(n * old_cap, -1), states.reshape(n * old_cap, -1)
        nm[sh[:k], idx[:k]] = fm[live]
        ns[sh[:k], idx[:k]] = fs[live]
        nv[sh[:k], idx[:k]] = True
        nn[sh[:k], idx[:k]] = cur_new.reshape(-1)[live]

    def put(x):
        return jax.device_put(jnp.asarray(x), NamedSharding(mesh, P(axis)))

    return (put(nm.reshape(n * new_cap, -1)),
            put(ns.reshape(n * new_cap, -1)),
            put(nv.reshape(n * new_cap))) + tuple(carry[3:17]) \
        + (put(nn.reshape(n * new_cap)),)


def check_sharded(model: JaxModel,
                  history: Optional[History] = None,
                  prepared: Optional[PreparedHistory] = None,
                  mesh: Optional[Mesh] = None,
                  axis: str = "model",
                  capacity_per_shard: int = 1024,
                  max_capacity_per_shard: int = 65536,
                  chunk: int = 2048,
                  max_window: int = 4096,
                  work_budget: Optional[int] = None) -> Dict[str, Any]:
    """Frontier-sharded linearizability check of one history.

    ``work_budget`` overrides the per-dispatch closure-iteration budget
    (None = the capacity-scaled default, see _sharded_runner; tests pass a
    tiny value to force the mid-chunk pause/resume path on small meshes)."""
    assert mesh is not None, "check_sharded requires a mesh"
    from jepsen_tpu.checker.wgl_tpu import _round_window
    p = prepared if prepared is not None else prepare(
        history, model, max_window=max_window)
    window = _round_window(p.window)
    ev = events_array(p, chunk)
    n_events = ev.shape[0]
    # One chunk-sized NOP cushion so a mid-chunk resume offset can always
    # slice a full chunk without clamping back into real events (see
    # wgl_tpu.check).
    ev = np.concatenate([ev, np.zeros((chunk, ev.shape[1]), ev.dtype)])
    ev[n_events:, 0] = EV_NOP
    n = mesh.shape[axis]

    def put_repl(x):
        return jax.device_put(jnp.asarray(x), NamedSharding(mesh, P()))

    # Whole event stream uploaded once (replicated); chunks are sliced
    # device-side — a per-chunk host->device put blocks the dispatch
    # loop (see wgl_tpu.check).
    ev_dev = put_repl(ev)
    slice_chunk = _chunk_slicer(chunk)

    gw = chosen_gwords(p)
    cap = capacity_per_shard
    max_cap_reached = cap  # diagnostics: how far escalation actually went
    run = _sharded_runner(model, window, cap, mesh, axis, gw, work_budget)
    carry = _initial_carry(model, window, cap, n, mesh, axis)
    # (peak, events-consumed) samples since the last capacity change (see
    # wgl_tpu.check: shrink-back weighs samples by events covered because a
    # budget-paused dispatch can cover anywhere from 0 to chunk events).
    SHRINK_WINDOW = 4 * chunk
    recent_peaks: deque = deque()
    inflight: deque = deque()  # (pos, carry_before, carry_after, flags)
    pos = 0
    failed = overflow = False
    done = carry
    # Pipelined dispatch (see wgl_tpu.check): speculation past a failure or
    # overflow is safe because the failed/overflow lanes gate all updates in
    # event_step — speculative chunks are simply discarded on resume.
    # Pipelining pays where the device→host flags transfer has real latency
    # (an accelerator); on the host-platform CPU mesh the transfer is a
    # memcpy and extra in-flight chunks only cost memory (measured ~20%
    # slower), so keep the pipeline depth at 1 there.
    lookahead = (LOOKAHEAD
                 if mesh.devices.flat[0].platform != "cpu" else 1)
    while True:
        while len(inflight) < lookahead and pos < n_events:
            prev = carry
            carry, flags = run(carry, slice_chunk(ev_dev, pos))
            inflight.append((pos, prev, carry, flags))
            pos += chunk
        if not inflight:
            break
        cpos, prev, after, flags = inflight.popleft()
        fl = np.asarray(flags)
        failed, overflow = bool(fl[0]), bool(fl[1])
        peak = int(fl[2])  # global (psum'd) distinct-config high-water mark
        consumed = int(fl[3])
        if overflow and cap < max_capacity_per_shard:
            # Escalate straight to a capacity the observed global peak says
            # is enough (peak may itself be clipped, so the loop can escalate
            # again), and resume from the pre-chunk snapshot: no restart.
            old = cap
            while cap < max_capacity_per_shard and cap * n < 2 * peak:
                cap = min(cap * 4, max_capacity_per_shard)
            if cap == old:
                cap = min(old * 4, max_capacity_per_shard)
            max_cap_reached = max(max_cap_reached, cap)
            recent_peaks.clear()
            inflight.clear()
            run = _sharded_runner(model, window, cap, mesh, axis, gw,
                                  work_budget)
            carry = _resize_carry_sharded(prev, n, old, cap, mesh, axis)
            pos = cpos
            overflow = False
            continue
        done = after
        if failed or overflow:
            break
        recent_peaks.append((peak, consumed))
        covered = sum(e for _, e in recent_peaks)
        while len(recent_peaks) > 1 and covered - recent_peaks[0][1] >= \
                SHRINK_WINDOW:
            covered -= recent_peaks.popleft()[1]
        resumed = consumed < chunk
        if cap > capacity_per_shard and covered >= SHRINK_WINDOW:
            # Transient crash-burst demand has passed: drop back to a
            # cheaper-per-round engine once 2x the recent global peak fits.
            need = 2 * max(pk for pk, _ in recent_peaks)
            target = cap
            while (target > capacity_per_shard
                   and (target // 4) * n >= need):
                target //= 4
            # an escalation clamped to max_capacity can sit off the
            # power-of-4 lattice; never shrink below the configured floor
            target = max(target, capacity_per_shard)
            if target < cap:
                old = cap
                cap = target
                recent_peaks.clear()
                inflight.clear()
                run = _sharded_runner(model, window, cap, mesh, axis, gw,
                                      work_budget)
                carry = _resize_carry_sharded(after, n, old, cap, mesh, axis)
                pos = cpos + consumed
                continue
        if resumed:
            # Closure budget exhausted mid-chunk: discard speculative
            # dispatches and resume exactly where the engine stopped (the
            # single-chip watchdog-bound pattern, wgl_tpu.check).
            inflight.clear()
            carry = after
            pos = cpos + consumed
    carry = done

    explored = int(carry[9])
    if overflow:
        return {"valid": "unknown", "analyzer": "wgl-tpu-sharded",
                "error": f"capacity exceeded at {cap}x{n}",
                "configs-explored": explored}
    if not failed:
        return {"valid": True, "analyzer": "wgl-tpu-sharded",
                "configs-explored": explored, "shards": n,
                "capacity": cap * n,
                "max-capacity-reached": max_cap_reached * n}
    # witness: frontier emptied across ALL shards; refuting op attached
    return {"valid": False, "analyzer": "wgl-tpu-sharded",
            "op": p.ops[int(carry[7])].to_dict(),
            "configs-explored": explored, "shards": n}
