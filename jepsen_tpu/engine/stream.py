"""Pulse: the device-resident streaming WGL tier.

The host monitor (:mod:`jepsen_tpu.monitor.epochs`) keeps one
:class:`~jepsen_tpu.monitor.epochs.KeyFrontier` per key and steps its
configuration search in Python.  This module keeps the same frontier
*on the device*: the config-set carry of the compiled WGL engine
(:func:`jepsen_tpu.checker.wgl_tpu.make_engine`) persists between
monitor epochs — donated in place, never re-uploaded — and each epoch
dispatches ONLY the ops that arrived since the last one, padded onto
the epoch-events rung of the shape ladder
(:func:`jepsen_tpu.engine.ladder.epoch_events_bucket`).  Per-epoch cost
is therefore bounded by new-ops work, flat in total history length.

Division of labour:

- :class:`_EventCursor` — the host :class:`KeyFrontier` with its closure
  unplugged: the inherited horizon loop does all the stream-order
  resolution (fail pairs removed, crashed ops ghosted, unconstraining
  crashed reads dropped, LIFO slot reuse — exactly ``checker.prep``'s
  event stream by construction), but ENTER/RETURN *emit device event
  rows* instead of stepping configurations.
- :class:`DeviceKeyFrontier` — owns the resident carry and the
  escalation ladder.  Soundness contract, in order of degradation:
  a device ``failed`` flag is never trusted directly — the raw prefix is
  replayed through a fresh host :class:`KeyFrontier` and ITS refutation
  dict is adopted verbatim (byte-identical to the host tier; a
  refutation on a prefix is final, so confirming on the same prefix is
  sound).  Capacity overflow climbs the ``next_capacity`` ladder
  (replaying the full event stream into a fresh carry — donation means
  no snapshots); at the ceiling, and on any device error or monitor-lane
  timeout, the frontier falls back STICKY to the host tier: unknown or
  host-verdict, never a fabricated false.
- :class:`StreamWglEpochEngine` — the per-key router, differing from
  :class:`WglEpochEngine` only in its frontier factory.

The engine is built LEAN (``gwords=0``): ghost subsumption is an
optimization, not a soundness condition, and the streaming cursor cannot
assign compact ghost positions online (prepare() numbers classes after
seeing the whole history).  Ghost-heavy streams simply explore more
configs, overflow earlier, and escalate — the ladder absorbs it.

Every compiled epoch executable is keyed ``("streamv", model, window,
capacity, epoch-bucket, ...)`` in the shared bounded engine cache, so N
concurrent monitored streams on the same rungs share ONE executable and
the steady state recompiles nothing.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import numpy as np

from jepsen_tpu.checker.wgl_tpu import (
    CLOSURE_WORK_BUDGET, EV_ENTER, EV_NOP, EV_RETURN, make_engine,
)
from jepsen_tpu.engine.cache import CACHE as _ENGINE_CACHE
from jepsen_tpu.engine.ladder import (
    MAX_WGL_CAPACITY, MIN_EVENTS_BUCKET, MIN_WIDTH_BUCKET,
    epoch_events_bucket, next_capacity, pow2_at_least, round_window,
    wgl_start_capacity)
from jepsen_tpu.monitor.epochs import KeyFrontier, WglEpochEngine
from jepsen_tpu.obs.hist import timed_first_call
from jepsen_tpu.ops import dedup as _dedup
from jepsen_tpu.parallel.batch import donate_carry_argnums

#: capacity-escalation factor (same rung spacing as the batch tier)
CAPACITY_GROWTH = 8


def stream_engine_rungs(width: int, n_new: int):
    """The (window, start-capacity, epoch-chunk) rung triple for a
    stream whose pending window high-water is ``width`` with ``n_new``
    undispatched event rows.  Pure function of the (width bucket,
    epoch-events bucket) pair — the raw inputs are quantized here, so
    equal buckets always compile equal shapes (the TRACE02 stream leg
    asserts exactly this)."""
    wb = pow2_at_least(max(1, width), MIN_WIDTH_BUCKET)
    return (round_window(wb),
            wgl_start_capacity(MIN_EVENTS_BUCKET, wb),
            epoch_events_bucket(n_new))


def monitor_dispatcher(service):
    """The service's monitor-lane dispatch callable (device work rides
    the scheduler's device-loop thread, serialized with serve traffic),
    or None when no scheduler is attached — the frontier then runs its
    dispatches inline."""
    sched = getattr(service, "_sched", None)
    if sched is None or not hasattr(sched, "monitor_call"):
        return None
    return sched.monitor_call


class _EventCursor(KeyFrontier):
    """KeyFrontier's stream-order event loop with the configuration
    search unplugged: ENTER/RETURN emit ``checker.prep``-format device
    event rows ([kind, slot, f, a, b, op_id, ghost, gcls, grank, gpos])
    into ``self.rows``.  Ghost class columns are emitted inert
    (gcls=-1): the stream engine is always LEAN, where they are unused.
    Never refutes, never explodes — the device owns the verdict."""

    def __init__(self, model, jax_model, max_configs: int = 2_000_000):
        super().__init__(model, max_configs=max_configs)
        self.jax_model = jax_model
        self.rows: List[List[int]] = []
        self._slot_opid: Dict[int, int] = {}
        self.op_seq = 0

    def _enter(self, eff, ghost, comp) -> None:
        s = self._alloc_slot()
        self.window[s] = eff
        self.ops_entered += 1
        f, a, b = self.jax_model.encode_op(eff)
        op_id = self.op_seq
        self.op_seq += 1
        self._slot_opid[s] = op_id
        self.rows.append([EV_ENTER, s, int(f), int(a), int(b), op_id,
                          1 if ghost else 0, -1, 0, 0])
        if ghost:
            self.ghost_mask |= 1 << s
            self.n_ghosts += 1
        elif comp is not None:
            self._return_slot[comp.index] = s

    def _return(self, slot, comp) -> None:
        op_id = self._slot_opid.pop(slot, 0)
        self.rows.append([EV_RETURN, slot, 0, 0, 0, op_id, 0, -1, 0, 0])
        del self.window[slot]
        self._free.append(slot)
        self.ops_checked += 1


class DeviceKeyFrontier:
    """One key's WGL frontier, resident on the device between epochs.

    Same surface as :class:`KeyFrontier` (feed / advance / finalize /
    pending_ops / verdict, plus the counters the epoch engine sums), so
    the monitor, the verdict channel, and resume.py cannot tell the
    tiers apart.  ``self.prefix`` always retains the raw fed ops: it is
    the replay source for escalation, refutation confirmation, and the
    sticky host fallback."""

    def __init__(self, jax_model, model, max_configs: int = 2_000_000,
                 capacity: Optional[int] = None,
                 max_capacity: Optional[int] = None, dispatcher=None):
        self.jax_model = jax_model
        self.model = model
        self.max_configs = max_configs
        self.capacity_opt = capacity
        self.max_capacity = (MAX_WGL_CAPACITY
                             if max_capacity is None else max_capacity)
        self.prefix: List[Any] = []
        self.result: Optional[Dict[str, Any]] = None
        self.exploded: Optional[str] = None
        self.fallback_reason: Optional[str] = None
        self.epoch_dispatches = 0
        self.escalations = 0
        self._cursor = _EventCursor(model, jax_model,
                                    max_configs=max_configs)
        self._dispatcher = dispatcher
        self._host: Optional[KeyFrontier] = None   # sticky fallback
        self._carry = None
        self._applied = 0                          # rows in the carry
        self._explored = 0
        self._finalizing = False
        window, start_cap, _ = stream_engine_rungs(1, 1)
        self._window = window
        self._capacity = capacity or start_cap

    # -- ingest / epoch surface -------------------------------------------
    def feed(self, op) -> None:
        self.prefix.append(op)
        (self._host if self._host is not None else self._cursor).feed(op)

    def advance(self) -> Optional[Dict[str, Any]]:
        if self.result is not None or self.exploded is not None:
            self._cursor._stream.clear()
            return None
        if self._host is not None:
            r = self._host.advance()
            self.result = self._host.result
            self.exploded = self._host.exploded
            return r
        before = self.result
        self._cursor.advance()      # emits rows; cannot refute or explode
        self._advance_device()
        return self.result if self.result is not before else None

    def finalize(self) -> None:
        self._finalizing = True
        if self._host is not None:
            self._host.finalize()
            self.result = self._host.result
            self.exploded = self._host.exploded
            return
        if self.result is not None or self.exploded is not None:
            return
        self._cursor.finalize()
        self._advance_device()

    def pending_ops(self) -> int:
        return (self._host if self._host is not None
                else self._cursor).pending_ops()

    @property
    def ops_entered(self) -> int:
        return (self._host if self._host is not None
                else self._cursor).ops_entered

    @property
    def ops_checked(self) -> int:
        return (self._host if self._host is not None
                else self._cursor).ops_checked

    @property
    def n_explored(self) -> int:
        if self._host is not None:
            return self._host.n_explored
        return self._explored

    def verdict(self) -> Dict[str, Any]:
        if self._host is not None:
            return self._host.verdict()     # byte-identical host tier
        if self.result is not None:
            return dict(self.result)        # adopted host refutation
        if self.exploded is not None:
            return {"valid": "unknown", "analyzer": "wgl-stream",
                    "error": self.exploded,
                    "configs-explored": self._explored}
        live = (int(np.asarray(self._carry[2]).sum())
                if self._carry is not None else 1)
        return {"valid": True, "analyzer": "wgl-stream",
                "configs-explored": self._explored,
                "final-configs-count": live,
                "window": self._window, "capacity": self._capacity}

    # -- device driver ----------------------------------------------------
    def _engine(self, ep_bucket: int):
        m = self.jax_model
        key = ("streamv", m.name, m.variant, m.state_size,
               tuple(m.init_state_array().tolist()), self._window,
               self._capacity, ep_bucket, _dedup.N_PROBES,
               _dedup.WIDE_SORT_ROWS, _dedup.SUBSUME, CLOSURE_WORK_BUDGET)
        hit = _ENGINE_CACHE.get(key)
        if hit is not None:
            return hit
        carry0, _, run_chunk = make_engine(m, self._window, self._capacity,
                                           gwords=0)
        # Donated carry: the frontier's config set updates in place and
        # stays resident across epochs.  Donation forbids snapshots, so
        # every escalation replays the full event stream instead of
        # resuming — rungs only grow, so each is paid at most once.
        run = timed_first_call(
            jax.jit(run_chunk, donate_argnums=donate_carry_argnums()),
            f"compile:streamv:{m.name}:w{self._window}"
            f":c{self._capacity}:e{ep_bucket}")
        return _ENGINE_CACHE.put(key, (carry0, run))

    def _grow_window(self, width: int) -> None:
        window, start_cap, _ = stream_engine_rungs(width, 1)
        self._window = window
        self._capacity = max(self._capacity,
                             self.capacity_opt or start_cap)
        self._carry = None
        self._applied = 0
        self.escalations += 1

    def _advance_device(self) -> None:
        import jax.numpy as jnp
        cur = self._cursor
        if cur._next_slot > self._window:
            self._grow_window(cur._next_slot)
        rows = cur.rows
        while (self.result is None and self.exploded is None
               and self._host is None and self._applied < len(rows)):
            remaining = len(rows) - self._applied
            b = epoch_events_bucket(remaining)
            take = min(remaining, b)
            chunk = np.zeros((b, 10), np.int32)
            chunk[:, 0] = EV_NOP
            chunk[:take] = np.asarray(
                rows[self._applied:self._applied + take], np.int32)
            carry0, run = self._engine(b)
            carry_in = self._carry if self._carry is not None else carry0()

            def dispatch(carry_in=carry_in, run=run, chunk=chunk):
                carry, flags = run(carry_in, jnp.asarray(chunk))
                return carry, np.asarray(flags)

            try:
                if self._dispatcher is not None:
                    carry, fl = self._dispatcher(dispatch)
                else:
                    carry, fl = dispatch()
            except Exception as e:  # noqa: BLE001 — timeout, stopped
                # loop, or device error: the carry's state is no longer
                # trustworthy (a timed-out dispatch may still land on
                # it later), so the device path is abandoned for good.
                self._fall_back(f"stream dispatch failed: {e}")
                return
            self._carry = carry
            self.epoch_dispatches += 1
            failed, overflow = bool(fl[0]), bool(fl[1])
            consumed = int(fl[3])
            if overflow:
                # Overflow may have dropped configurations, which could
                # fake an empty-survivor refutation — escalate FIRST and
                # never read the failed flag off an overflowed chunk.
                nxt = next_capacity(self._capacity, self.max_capacity,
                                    growth=CAPACITY_GROWTH)
                if nxt is None:
                    self._fall_back("configuration capacity exceeded at "
                                    f"{self._capacity}")
                    return
                self._capacity = nxt
                self._carry = None
                self._applied = 0
                self.escalations += 1
                continue
            self._applied += min(consumed, take)
            if failed:
                self._confirm_refutation()
                return
            # consumed < take is a closure-budget pause: loop around and
            # redispatch the remainder with a fresh budget.
        if self._carry is not None and self._host is None:
            self._explored = int(np.asarray(self._carry[9]))

    # -- degradation ladder ----------------------------------------------
    def _host_replay(self) -> KeyFrontier:
        f = KeyFrontier(self.model, max_configs=self.max_configs)
        for op in self.prefix:
            f.feed(op)
        if self._finalizing:
            f.finalize()
        else:
            f.advance()
        return f

    def _confirm_refutation(self) -> None:
        """The device flagged a refutation: replay the raw prefix through
        the host tier and adopt ITS result dict verbatim — refutations
        stay byte-identical to the host monitor's.  A disagreeing replay
        (host says valid or explodes) degrades to unknown, never to a
        device-only false."""
        f = self._host_replay()
        if f.result is not None:
            self.result = f.result
        elif f.exploded is not None:
            self.exploded = f.exploded
        else:
            self.exploded = ("device refutation unconfirmed by host "
                             "replay")

    def _fall_back(self, reason: str) -> None:
        """Sticky host fallback: replay the prefix into a fresh host
        frontier and route every later feed/advance through it.  The
        device carry is dropped and never consulted again."""
        self.fallback_reason = reason
        self._carry = None
        f = self._host_replay()
        self._host = f
        self.result = f.result
        self.exploded = f.exploded


class StreamWglEpochEngine(WglEpochEngine):
    """WglEpochEngine whose frontiers live on the device.  ``model`` may
    be a registry name (resolves both tiers) or a host model paired with
    an explicit ``jax_model``; without a device model the factory simply
    hands out host frontiers — the knob degrades, it never breaks."""

    def __init__(self, model, jax_model=None, independent: bool = False,
                 max_configs: int = 2_000_000, keep_prefix: bool = False,
                 service=None, capacity: Optional[int] = None,
                 max_capacity: Optional[int] = None):
        if jax_model is None and isinstance(model, str):
            from jepsen_tpu.models import get_model
            jax_model = get_model(model)
        if isinstance(model, str) and jax_model is not None:
            model = jax_model.cpu_model()   # host tier for replays
        super().__init__(model, independent=independent,
                         max_configs=max_configs, keep_prefix=keep_prefix)
        self.jax_model = jax_model
        self.service = service
        self.capacity = capacity
        self.max_capacity = max_capacity

    def _new_frontier(self):
        if self.jax_model is None:
            return super()._new_frontier()
        return DeviceKeyFrontier(self.jax_model, self.model,
                                 max_configs=self.max_configs,
                                 capacity=self.capacity,
                                 max_capacity=self.max_capacity,
                                 dispatcher=monitor_dispatcher(self.service))

    def counters(self) -> Dict[str, int]:
        c = super().counters()
        c["epoch-dispatches"] = sum(
            getattr(f, "epoch_dispatches", 0)
            for f in self.frontiers.values())
        c["fallbacks"] = sum(
            1 for f in self.frontiers.values()
            if getattr(f, "fallback_reason", None) is not None)
        return c
