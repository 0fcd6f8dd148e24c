"""Frontier sharding: one history's configuration set split across a mesh.

The long-history analog of sequence parallelism (SURVEY.md §5.7): instead of
splitting a history into short per-key pieces the way the reference must
(jepsen/src/jepsen/independent.clj:1-7), the configuration frontier itself is
sharded over the ``model`` mesh axis.  Each device expands its local shard of
configurations (vmapped model steps), candidates are exchanged with
all_gather over ICI, every device deduplicates the global set identically
(replicated sort), and keeps its deterministic slice.  Failure/overflow flags
are psum-reduced so all shards agree.

There is no host loop here: the driver is ``wgl_tpu._check``, the one the
single device has, over the placement :class:`OnMesh`.  So the sharded search
dispatches, polls, pauses, grows and shrinks as the one-chip search does: the
event cursor rides on the device with the carry, a chunk dispatched behind a
budget pause goes on from the pause, an overflow resumes from the pre-chunk
snapshot at a capacity the global peak says is enough, and the engine drops
back to a cheaper shape once a burst has passed.  What the mesh adds is the
runner (a ``shard_map`` of ``make_engine``'s ``run_chunk``) and the carry's
re-laying, shard by shard.

The normal path reaches it from ``engine.fission.split_check``: a history
whose frontier outgrew one chip's last rung, with nothing to split by
component and more than one chip attached.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map as _shard_map

from jepsen_tpu.checker import wgl_tpu
from jepsen_tpu.checker.prep import PreparedHistory
from jepsen_tpu.engine.cache import CACHE as _ENGINE_CACHE
from jepsen_tpu.engine.witness import WITNESS_BUDGET
from jepsen_tpu.history import History
from jepsen_tpu.models.base import JaxModel
from jepsen_tpu.obs.hist import timed_first_call
from jepsen_tpu.obs.recorder import span
from jepsen_tpu.ops import dedup as _dedup
from jepsen_tpu.parallel.mesh import make_mesh

ANALYZER = "wgl-tpu-sharded"

# ---------------------------------------------------------------------------
# Counters (check_stats idiom): how much of the stream ran sharded, and how
# evenly the rows lay over the shards
# ---------------------------------------------------------------------------

_STATS_LOCK = threading.Lock()


def _zero_stats() -> Dict[str, int]:
    return {"events_sharded": 0, "events_total": 0,
            "rows_live_min": 0, "rows_live_max": 0}


_STATS = _zero_stats()


def sharded_stats() -> Dict[str, int]:
    """Sums over every :func:`check_sharded` of this process:
    ``events_sharded``, the events its accepted polls consumed;
    ``events_total``, those and the events a one-chip search had consumed
    before the snapshot it handed over (``resume``); ``rows_live_min`` and ``rows_live_max``,
    the live rows of the emptiest and of the fullest shard at the end of
    each accepted chunk, summed over the polls.  ``rows_live_min /
    rows_live_max`` is how evenly the frontier lay over the shards: each
    shard expands its own rows, and the fullest shard's candidates pick the
    round's merge width for all."""
    with _STATS_LOCK:
        return dict(_STATS)


def reset_sharded_stats() -> None:
    with _STATS_LOCK:
        _STATS.update(_zero_stats())


# carry layout (wgl_tpu.make_engine): mask[C,MW], states[C,S], valid[C] and,
# last, cur_new[C] hold rows and are sharded; the fourteen between are
# per-slot arrays and scalars, identical across shards, hence replicated.
def _carry_specs(axis: str):
    return (P(axis),) * 3 + (P(),) * 14 + (P(axis),)


def mesh_program(model: JaxModel, window: int, capacity: int, gwords: int,
                 chunk: int, mesh: Mesh, axis: str, work_budget: int):
    """The jitted ``run(carry, cursor, ev_dev) -> (carry', cursor + consumed,
    flags)`` of :meth:`OnMesh.runner`: ``make_engine``'s ``run_chunk`` with
    ``capacity`` rows a shard under a ``shard_map`` over ``axis``, slicing
    its own ``chunk`` events out of the replicated stream at the cursor.
    ``flags`` are ``run_chunk``'s five and, after them, the live rows of
    the emptiest and of the fullest shard."""
    _, _, run_chunk = wgl_tpu.make_engine(
        model, window, capacity, axis_name=axis,
        num_shards=mesh.shape[axis], gwords=gwords, work_budget=work_budget)

    def run_at(carry, cursor, ev_dev):
        # the slice behind a barrier: wgl_tpu._get_run_chunk says why
        events = lax.dynamic_slice_in_dim(
            *lax.optimization_barrier((ev_dev, cursor)), chunk)
        carry, flags = run_chunk(carry, events)
        live = lax.all_gather(carry[2].sum().astype(jnp.int32), axis)
        flags = jnp.concatenate([flags, jnp.stack([live.min(), live.max()])])
        return carry, cursor + flags[3], flags

    specs = (_carry_specs(axis), P(), P())
    # Replication checking off (check_vma): closure dedup sorts the
    # *gathered* global row set, so every shard computes bit-identical
    # "replicated" scalars (counts, flags), but the varying-axes checker
    # can't prove that post-all_gather.
    return jax.jit(_shard_map(run_at, mesh=mesh, in_specs=specs,
                              out_specs=specs, check_vma=False))


class OnMesh:
    """``wgl_tpu._check``'s placement (see :class:`wgl_tpu.OneDevice`) with
    the frontier divided over ``mesh``'s ``axis``: ``capacity`` rows on
    each of its ``shards`` devices, every closure round's sort over the
    gathered ``capacity * shards``."""

    analyzer = ANALYZER

    def __init__(self, mesh: Mesh, axis: str = "model",
                 work_budget: Optional[int] = None) -> None:
        self.mesh, self.axis, self.work_budget = mesh, axis, work_budget
        self.shards = mesh.shape[axis]
        # this call's share of sharded_stats(), read by check_sharded
        self.did = _zero_stats()

    @property
    def lookahead(self) -> int:
        # Pipelining pays where the device→host flags transfer has real
        # latency (an accelerator); on the host-platform CPU mesh the
        # transfer is a memcpy and extra in-flight chunks only cost memory
        # (measured ~20% slower), so keep the pipeline depth at 1 there.
        return (wgl_tpu.LOOKAHEAD
                if self.mesh.devices.flat[0].platform != "cpu" else 1)

    def _put(self, x, spec=P()):
        return jax.device_put(jnp.asarray(x), NamedSharding(self.mesh, spec))

    def stage(self, ev: np.ndarray):
        # the whole stream once, replicated; the runner slices it
        return self._put(ev)

    def runner(self, model: JaxModel, window: int, capacity: int,
               gwords: int, chunk: int):
        """``(carry0, run)`` as ``wgl_tpu._get_run_chunk`` gives them: ONE
        program a dispatch, ``run(carry, cursor, ev_dev) -> (carry', cursor
        + consumed, flags)``, here a ``shard_map`` over the mesh axis whose
        flags end with the live rows of the emptiest and of the fullest
        shard (``polled`` sums them)."""
        n, axis, mesh = self.shards, self.axis, self.mesh
        # Each shard's closure round sorts the *gathered global* set, so a
        # round costs what capacity * n rows cost: the per-dispatch budget
        # (the watchdog bound, wgl_tpu.closure_budget) divides by that.
        budget = (self.work_budget if self.work_budget is not None
                  else wgl_tpu.closure_budget(capacity * n))
        key = ("shardv", model.name, model.variant, model.state_size,
               tuple(model.init_state_array().tolist()), window, capacity,
               gwords, chunk, mesh, axis, budget, _dedup.N_PROBES,
               _dedup.WIDE_SORT_ROWS, _dedup.SUBSUME)
        hit = _ENGINE_CACHE.get(key)
        if hit is not None:
            return hit
        run = timed_first_call(
            mesh_program(model, window, capacity, gwords, chunk, mesh, axis,
                         budget),
            f"compile:shardv:{model.name}:w{window}:c{capacity}x{n}")

        def carry0():
            return _initial_carry(model, window, capacity, n, mesh, axis)

        return _ENGINE_CACHE.put(key, (carry0, run))

    def grow(self, carry, capacity: int):
        return _resize_carry_sharded(
            carry, self.shards, carry[2].shape[0] // self.shards, capacity,
            self.mesh, self.axis)

    shrink = grow

    def adopt(self, carry, capacity: int):
        # a one-device carry's rows, cut into ``shards`` blocks where they
        # lie (the next merge deals the gathered set anew anyway); the rest
        # replicated
        return self.grow(tuple(
            self._put(np.asarray(x), spec)
            for x, spec in zip(carry, _carry_specs(self.axis))), capacity)

    def polled(self, flags: np.ndarray, consumed: int) -> None:
        self.did["events_sharded"] += consumed
        self.did["rows_live_min"] += int(flags[5])
        self.did["rows_live_max"] += int(flags[6])


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
                  chunk: Optional[int] = None,
                  max_window: int = 4096,
                  work_budget: Optional[int] = None,
                  devices: Optional[Sequence[Any]] = None,
                  resume: Optional[wgl_tpu.Snapshot] = None,
                  explain: bool = True, cancel=None,
                  witness_budget: int = WITNESS_BUDGET,
                  growth: int = 4) -> Dict[str, Any]:
    """Frontier-sharded linearizability check of one history:
    ``wgl_tpu.check`` with the frontier over ``mesh``'s ``axis`` (or over
    ``devices``, one axis of them all), capacities counted in rows a shard;
    from event 0, or from where a one-device search stopped (``resume``,
    the :class:`wgl_tpu.Snapshot` its ``check`` left).
    A refutation carries the refuting op and the host oracle's witness as
    ``wgl_tpu.check``'s does; a frontier over ``max_capacity_per_shard`` on
    every shard is ``unknown`` with ``capacity-exceeded``.

    ``work_budget`` overrides the per-dispatch closure-iteration budget
    (None = the capacity-scaled default, see :meth:`OnMesh.runner`; tests
    pass a tiny value to force the mid-chunk pause path on small meshes).

    Under its ``drivers.shard`` span, which closes with what
    ``drivers.check`` closes with and ``shards``, ``cap_per_shard`` (the
    largest reached), ``pauses``, ``resized``."""
    if mesh is None:
        if not devices:
            raise ValueError("check_sharded requires a mesh or devices")
        mesh = make_mesh((1, len(devices)), devices=devices)
    place = OnMesh(mesh, axis, work_budget)
    with span("drivers.shard", shards=place.shards) as sp:
        try:
            return wgl_tpu._check(
                sp, place, model, history, prepared, capacity_per_shard,
                max_capacity_per_shard, chunk, max_window, explain, cancel,
                witness_budget, growth, resume=resume)
        finally:
            did = sp.args
            sp.set(cap_per_shard=did.get("max_capacity"),
                   pauses=did.get("resumes"),
                   resized=did.get("grows", 0) + did.get("shrinks", 0))
            place.did["events_total"] = place.did["events_sharded"] + (
                resume.cursor if resume is not None else 0)
            with _STATS_LOCK:
                for k in _STATS:
                    _STATS[k] += place.did[k]
