"""The continuous-batch scheduler: one device loop draining a cell queue.

The device is a single serially-dispatched resource, so the scheduler is
one thread: each iteration it picks the most urgent shape bucket
(tenant priority class, then earliest deadline, FIFO within a deadline
class), packs up to a lane
bucket's worth of that bucket's cells into ONE vmapped dispatch — wgl
cells through parallel.batch.check_batch, elle cells through
elle_tpu.engine.check_batch — and loops.  New cells admitted while a
dispatch is on the device are seen at the very next iteration: requests
continuously join batches instead of waiting for a convoy to finish
(continuous batching, the same scheduler shape as an inference server).

Guarantees:

- cells whose request deadline has already passed are resolved
  ``unknown`` (never dispatched, never ``false``) — deadline semantics
  match check_safe's budget degradation;
- a device failure downgrades the affected cells to the host tier
  (wgl_cpu / elle engine="cpu") with a ``fallback`` annotation, exactly
  like checker.linearizable's degradation chain — a device error never
  decides a verdict;
- lane padding (to power-of-two lane buckets, for engine-cache
  stability) is measured: every dispatch reports used vs padded lanes to
  the metrics registry.
"""

from __future__ import annotations

import logging
import os
import threading
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from jepsen_tpu.engine import ladder
from jepsen_tpu.obs.recorder import RECORDER, adopt
from jepsen_tpu.serve.aggregate import aggregate, expired_result
from jepsen_tpu.serve.metrics import mono_now
from jepsen_tpu.serve.request import Cell, KIND_ELLE, KIND_WGL

log = logging.getLogger("jepsen.serve")

#: a bucket whose head cell has queued this long outranks deadline order
DEFAULT_AGE_S = 5.0


class Scheduler:
    def __init__(self, metrics, mesh=None, max_lanes: int = 64,
                 capacity: Optional[int] = None, max_capacity: int = 65536,
                 age_s: Optional[float] = DEFAULT_AGE_S, device=None):
        self.metrics = metrics
        self.mesh = mesh
        # A fleet worker's device pin: dispatches run under
        # jax.default_device(device) so N in-process workers partition the
        # host's devices instead of convoying on device 0.  None = the
        # backend default (the solo-service behaviour).
        self.device = device
        self.max_lanes = max(1, min(max_lanes, ladder.MAX_LANE_BUCKET))
        # None = derive the start capacity from each dispatch's bucket
        # shape (ladder.wgl_start_capacity); an int pins the old fixed
        # knob for every dispatch.
        self.capacity = capacity
        self.max_capacity = max_capacity
        self.age_s = age_s
        self._groups: Dict[Tuple, deque] = {}
        self._depth = 0
        self._seq = 0               # admission order (FIFO tiebreak)
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._stop = False
        self._inflight = 0
        self._idle_listeners: List[Any] = []
        # monitor lane: thunks the streaming monitors want run on the
        # device-loop thread, between batch dispatches — the monitor's
        # epoch-advance chunks share the device with request traffic
        # without a second dispatch thread racing it (see monitor_call)
        self._monitor_lane: deque = deque()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="serve-scheduler")
        self._started = False

    # -- queue ------------------------------------------------------------
    def start(self) -> None:
        if not self._started:
            self._started = True
            self._thread.start()

    def offer(self, cells: List[Cell], block: bool, max_depth: int,
              timeout: Optional[float]) -> bool:
        """Admit a request's cells (all or nothing).  Blocks while the
        queue is above ``max_depth`` (backpressure); False = rejected."""
        deadline = (mono_now() + timeout) if timeout is not None \
            else None
        with self._cond:
            while not self._stop and self._depth + len(cells) > max_depth:
                if not block:
                    return False
                rem = None if deadline is None \
                    else deadline - mono_now()
                if rem is not None and rem <= 0:
                    return False
                if not self._cond.wait(timeout=rem if rem is not None
                                       else 0.1):
                    return False
            if self._stop:
                return False
            t_in = mono_now()
            for c in cells:
                c.seq = self._seq = self._seq + 1
                c.enqueued = t_in
                self._groups.setdefault(c.bucket, deque()).append(c)
            self._depth += len(cells)
            self._cond.notify_all()
            return True

    def depth(self) -> int:
        with self._lock:
            return self._depth

    def occupancy(self) -> Dict[str, Any]:
        """The autoscaler's input signals as first-class data: per-bucket
        queue depth and the oldest head wait-age (the same age the aged
        tier of :meth:`_take_group` acts on).  Rides in the metrics
        snapshot — and therefore in every telemetry push frame — via
        Metrics.bind_queue."""
        now = mono_now()
        with self._lock:
            buckets: Dict[str, int] = {}
            oldest = 0.0
            for key, dq in self._groups.items():
                if not dq:
                    continue
                buckets[str(key)] = len(dq)
                oldest = max(oldest, now - dq[0].enqueued)
            return {"depth": self._depth, "buckets": buckets,
                    "oldest-wait-s": round(oldest, 6)}

    def add_idle_listener(self, fn) -> None:
        """Drain hook: ``fn()`` fires on the device-loop thread (outside
        the lock) each time the scheduler goes idle — queue empty and
        nothing in flight.  The wire worker (serve/worker_main.py) stamps
        idle-age into its STATUS replies this way instead of polling the
        condition variable; a listener must be cheap and must not block,
        since it runs between dispatches."""
        with self._lock:
            self._idle_listeners.append(fn)

    def inflight(self) -> int:
        with self._lock:
            return self._inflight

    def alive(self) -> bool:
        """Is the device loop still able to make progress?  False once the
        thread died (a crash the loop's own try/except failed to contain)
        or a stop/kill landed — the fleet's heartbeat probes this."""
        with self._lock:
            return self._alive_locked()

    def _alive_locked(self) -> bool:
        """:meth:`alive` for callers already inside the scheduler lock
        (monitor_call's admission check)."""
        return (self._started and not self._stop
                and self._thread.is_alive())

    def monitor_call(self, fn, timeout: float = 300.0) -> Any:
        """Run ``fn()`` on the device-loop thread, between batch
        dispatches, and return its result (re-raising its exception).

        The streaming monitors (engine/stream.py) route their epoch
        chunk dispatches here when a service owns the device: the device
        is one serially-dispatched resource, so monitor work must
        interleave with request batches on the ONE loop thread instead
        of racing them from the monitor's thread.  Monitor thunks run
        before the next batch pick — an epoch chunk is small (one
        bucketed dispatch), so lane traffic cannot starve requests.

        When the loop is not running (never started, stopped, crashed),
        ``fn`` runs inline on the caller — the monitor still advances,
        just without interleaving.  The generous default timeout covers
        a first-call XLA compile landing in front of the thunk."""
        box: Dict[str, Any] = {}
        done = threading.Event()
        with self._cond:
            live = self._alive_locked()
            if live:
                self._monitor_lane.append((fn, box, done))
                self._cond.notify_all()
        if not live:
            return fn()
        if not done.wait(timeout):
            raise TimeoutError("monitor-lane dispatch timed out")
        if "error" in box:
            raise box["error"]
        return box["result"]

    def _drain_monitor_lane(self) -> List[Tuple[Any, Dict[str, Any],
                                                threading.Event]]:
        """Snapshot-and-clear the lane (caller holds the lock)."""
        lane = list(self._monitor_lane)
        self._monitor_lane.clear()
        return lane

    def evict_pending(self) -> List[Cell]:
        """Drain hook: pop every *queued* (not yet dispatched) cell and
        hand it back to the caller unresolved.  The fleet uses this to
        decommission a worker — its queue moves to a sibling instead of
        waiting out the corpse.  Cells already in a device dispatch are
        not evictable; they either resolve normally or hang with the
        worker (the router's hedge covers that window)."""
        with self._cond:
            out: List[Cell] = []
            for dq in self._groups.values():
                out.extend(dq)
                dq.clear()
            self._groups.clear()
            self._depth = 0
            self._cond.notify_all()
        return sorted(out, key=lambda c: c.seq)

    def kill(self) -> List[Cell]:
        """Abrupt death (the chaos harness's worker-crash fault): stop the
        loop WITHOUT draining and evict the queue.  In-flight dispatches
        may still finalize (a real crash can land before or after the ack;
        both must be survivable) — everything still queued is returned
        unresolved, exactly what a restart would recover from the
        journal."""
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        return self.evict_pending()

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Wait until the queue is empty and no dispatch is in flight."""
        deadline = (mono_now() + timeout) if timeout is not None \
            else None
        with self._cond:
            while self._depth > 0 or self._inflight:
                rem = None if deadline is None \
                    else deadline - mono_now()
                if rem is not None and rem <= 0:
                    return False
                self._cond.wait(timeout=rem if rem is not None else 0.1)
            return True

    def stop(self, drain: bool = True,
             timeout: Optional[float] = None) -> bool:
        """Stop the loop; with ``drain`` (default) the queue is emptied
        first — every admitted request still gets its verdict."""
        ok = self.drain(timeout) if drain else True
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._started:
            self._thread.join(timeout=30.0)
        return ok

    # -- the device loop --------------------------------------------------
    def _mega_eligible(self, bucket: Tuple) -> bool:
        """Small-bucket wgl cells route through the megabatch refill path
        (parallel.megabatch) when it is enabled: their steady-state
        traffic is thousands of short per-key lanes, exactly the shape
        the continuous-refill pipeline wins on.  Large event buckets and
        mesh-sharded dispatches keep the barrier path.

        Which model families qualify is the carry-descriptor registry
        (``engine.plugins.has_carry_descriptor``) — any family that
        registered its packed-carry descriptor bin-packs, not a
        hard-coded register list.  A family without one is never
        rejected: it simply falls back to the ``check_batch`` barrier
        path this method gates."""
        from jepsen_tpu.parallel.megabatch import megabatch_enabled
        if not (self.mesh is None and megabatch_enabled()
                and len(bucket) >= 4 and bucket[0] == KIND_WGL
                and bucket[2] <= ladder.MEGA_EVENTS_MAX):
            return False
        from jepsen_tpu.engine.plugins import has_carry_descriptor
        ident = bucket[1]
        name = ident[0] if isinstance(ident, tuple) and ident else ident
        return has_carry_descriptor(str(name))

    def _group_limit(self, bucket: Tuple) -> int:
        """Lanes to pop for one dispatch of this bucket: the megabatch
        path packs up to the mega lane ladder (grouped vmaps reusing one
        executable), the barrier path stays at max_lanes."""
        if self._mega_eligible(bucket):
            return ladder.mega_lane_bucket(ladder.MAX_MEGA_LANES)
        return self.max_lanes

    def _take_group(self) -> List[Cell]:
        """Pop the most urgent bucket's head cells (up to the bucket's
        group limit — max_lanes, or the mega lane ladder for megabatch-
        eligible buckets).

        Priority-then-deadline with aging: the plain pick is the
        smallest (-priority, deadline, seq) head — a tenant's priority
        class outranks deadline order (serve/tenants.py), deadline
        orders within a class — but a steady stream of urgent cells
        could then starve a far-deadline bucket forever — its compiled
        engine goes cold and the eventual dispatch pays a recompile.  So
        any bucket whose head has been queued longer than ``age_s``
        enters an aged tier that outranks deadline order (oldest wait
        first); picks decided by the aged tier are counted as
        ``aged_picks`` in the metrics snapshot."""
        best = None
        aged = None
        now = mono_now()
        for key, dq in self._groups.items():
            if not dq:
                continue
            k = dq[0].sort_key()
            if best is None or k < best[0]:
                best = (k, key)
            if self.age_s is not None:
                waited = now - dq[0].enqueued
                if waited >= self.age_s and (aged is None
                                             or waited > aged[0]):
                    aged = (waited, key)
        if best is None:
            return []
        if aged is not None and aged[1] != best[1]:
            best = (None, aged[1])
            self.metrics.inc("aged_picks")
        dq = self._groups[best[1]]
        limit = self._group_limit(best[1])
        out = []
        while dq and len(out) < limit:
            out.append(dq.popleft())
        if not dq:
            del self._groups[best[1]]
        self._depth -= len(out)
        return out

    def _loop(self) -> None:
        while True:
            with self._cond:
                while (self._depth == 0 and not self._monitor_lane
                       and not self._stop):
                    self._cond.wait(timeout=0.1)
                if self._stop and self._depth == 0:
                    # waiters must not hang on a dead loop: fail the
                    # lane so monitor_call raises instead of timing out
                    for _fn, box, done in self._drain_monitor_lane():
                        box["error"] = RuntimeError("scheduler stopped")
                        done.set()
                    return
                lane = self._drain_monitor_lane()
                cells = self._take_group()
                self._inflight = len(cells)
                self._cond.notify_all()  # depth dropped: wake producers
            # monitor thunks run outside the lock, before the batch —
            # an epoch chunk ahead of a dispatch, never inside either
            for fn, box, done in lane:
                try:
                    box["result"] = fn()
                    self.metrics.inc("monitor-epoch-dispatches")
                except Exception as e:  # noqa: BLE001 — caller re-raises
                    box["error"] = e
                finally:
                    done.set()
            if not cells:
                continue
            try:
                self._process(cells)
            except Exception:  # noqa: BLE001 — the loop must survive
                log.exception("scheduler dispatch failed terminally")
                for c in cells:
                    if c.result is None:
                        self._finalize(c, {
                            "valid": "unknown", "analyzer": "serve",
                            "error": "scheduler dispatch crashed"})
            finally:
                with self._cond:
                    self._inflight = 0
                    self._cond.notify_all()
                    listeners = (list(self._idle_listeners)
                                 if self._depth == 0 else [])
                # idle listeners fire outside the lock: a slow or buggy
                # listener must neither wedge producers nor kill the loop
                for fn in listeners:
                    try:
                        fn()
                    except Exception:  # noqa: BLE001
                        log.exception("scheduler idle listener failed")

    def _process(self, cells: List[Cell]) -> None:
        live: List[Cell] = []
        for c in cells:
            if c.request.expired():
                self.metrics.inc("deadline-expired")
                self._finalize(c, expired_result(c.request.kind))
            else:
                live.append(c)
        if not live:
            return
        for c in live:
            c.request.span("pack")
        t0 = mono_now()
        lanes = [c.history for c in live]
        kind = live[0].request.kind
        mega = kind == KIND_WGL and self._mega_eligible(live[0].bucket)
        if mega:
            # The megabatch packer buckets and pads lanes internally
            # (its width ladder is part of the engine-cache key); no
            # caller-side lane padding needed.
            pad = len(lanes)
            padded = lanes
        else:
            pad = ladder.lane_bucket(len(lanes), self.max_lanes)
            padded = lanes + [lanes[0]] * (pad - len(lanes))
        for c in live:
            c.request.span("dispatch")

        def run_dispatch():
            # the engines' spans nest under the batch's first request
            with adopt(live[0].request.trace_id, live[0].request.span_id):
                if kind == KIND_WGL:
                    return self._dispatch_wgl(live, padded, mega=mega)
                return self._dispatch_elle(live, padded)

        try:
            if self.device is not None:
                import jax
                with jax.default_device(self.device):
                    rs = run_dispatch()
            else:
                rs = run_dispatch()
        except Exception as e:  # noqa: BLE001 — device trouble, degrade
            log.warning("device dispatch failed (%s: %s); host fallback "
                        "for %d cell(s)", type(e).__name__, e, len(live))
            self.metrics.inc("host-fallbacks", len(live))
            rs = self._host_fallback(live, e)
        dt = mono_now() - t0
        self.metrics.dispatch(len(live), pad, dt)
        RECORDER.record(
            "dispatch", f"batch:{kind}:x{len(live)}",
            dur_s=dt,
            trace_id=live[0].request.trace_id,
            span_id=live[0].request.span_id,
            args={"lanes": len(live), "pad": pad, "mega": mega})
        for c, r in zip(live, rs):
            self._finalize(c, r)

    def _start_capacity(self, live: List[Cell], ev_bucket: int,
                        w_bucket: int) -> int:
        """Resolve the wgl start capacity: per-request ``capacity`` engine
        opts win, then the ``JEPSEN_TPU_WGL_CAPACITY`` env override, then
        a service-level fixed knob, then the bucket-shape derivation
        (ladder.wgl_start_capacity — the default).  Overflowing lanes
        still escalate automatically, so this only sets where the ladder
        starts."""
        explicit = [int(s.request.spec["capacity"]) for s in live
                    if s.request.spec.get("capacity") is not None]
        if explicit:
            return max(explicit)
        env = os.environ.get("JEPSEN_TPU_WGL_CAPACITY")
        if env:
            return max(1, int(env))
        if self.capacity is not None:
            return int(self.capacity)
        return ladder.wgl_start_capacity(ev_bucket, w_bucket)

    def _dispatch_wgl(self, live: List[Cell], padded: List[Any],
                      mega: bool = False) -> List[Dict[str, Any]]:
        from jepsen_tpu.parallel.batch import check_batch
        spec0 = live[0].request.spec
        _, _, ev_bucket, w_bucket = live[0].bucket
        cap = self._start_capacity(live, ev_bucket, w_bucket)
        max_cap = max(int(s.request.spec.get("max_capacity",
                                             self.max_capacity))
                      for s in live)
        if mega:
            from jepsen_tpu.parallel.megabatch import check_megabatch
            self.metrics.inc("megabatch-dispatches")
            self.metrics.inc("megabatch-lanes", len(padded))
            rs = check_megabatch(
                spec0["model"], padded, capacity=cap,
                max_capacity=max_cap, window_floor=w_bucket,
                ev_floor=ev_bucket,
                lanes=ladder.mega_lane_bucket(len(padded)))
        else:
            rs = check_batch(spec0["model"], padded, mesh=self.mesh,
                             capacity=cap, max_capacity=max_cap,
                             chunk=ladder.batch_chunk(len(padded), ev_bucket),
                             window_floor=w_bucket,
                             fission=spec0.get("fission"))
        return [self._explain_witness(c, r) for c, r in zip(live, rs)]

    def _explain_witness(self, cell: Cell, r):
        """Device lanes flag, the CPU recovers (engine.witness): the
        batched engines refute with the op alone, so when the submitter
        asked for an explanation the knossos-style witness is re-derived
        here, before the verdict leaves the dispatch path — the same
        discipline wgl_tpu.check applies directly.  The fission plane's
        witness-recovery re-checks depend on this seam: an explain=True
        re-submit to the refuting worker must come back witnessed.  A
        budget overrun degrades the witness to an error note, never the
        earned verdict."""
        if not (isinstance(r, dict) and r.get("valid") is False
                and "witness" not in r and isinstance(r.get("op"), dict)
                and cell.request.spec.get("explain")):
            return r
        from jepsen_tpu.engine.witness import cpu_witness
        model = cell.request.spec.get("model")
        idx = r["op"].get("index")
        failed = next((o for o in cell.history if o.index == idx), None)
        if model is None or failed is None:
            return r
        out = dict(r)
        # witness: CPU re-derivation on the refuted prefix rides the flagged op
        out["witness"] = cpu_witness(model, cell.history, failed)
        return out

    def _dispatch_elle(self, live: List[Cell],
                       padded: List[Any]) -> List[Dict[str, Any]]:
        from jepsen_tpu.elle_tpu.engine import check_batch
        spec0 = live[0].request.spec
        (_, _, n_bucket) = live[0].bucket
        remaining = [c.request.remaining_s() for c in live]
        known = [r for r in remaining if r is not None]
        budget = max(0.0, min(known)) if known else None
        rs = check_batch(padded,
                         workload=spec0.get("workload", "list-append"),
                         realtime=bool(spec0.get("realtime", False)),
                         consistency_models=spec0.get("consistency_models"),
                         engine=spec0.get("engine", "auto"),
                         mesh=self.mesh, budget_s=budget,
                         n_pad_floor=n_bucket)
        return rs[:len(live)]

    def _host_fallback(self, live: List[Cell],
                       exc: Exception) -> List[Dict[str, Any]]:
        """Per-cell host-tier re-check after a device dispatch failure."""
        out = []
        chain = [{"solver": f"{live[0].request.kind}-serve",
                  "error": str(exc), "error-type": type(exc).__name__}]
        for c in live:
            try:
                if c.request.kind == KIND_WGL:
                    from jepsen_tpu.checker import wgl_cpu
                    cm = c.request.spec["model"].cpu_model()
                    if cm is None:
                        r = {"valid": "unknown",
                             "error": "device failed; no host-tier model"}
                    else:
                        r = wgl_cpu.check(cm, c.history)
                else:
                    from jepsen_tpu.elle_tpu.engine import check_batch
                    r = check_batch(
                        [c.history], engine="cpu",
                        workload=c.request.spec.get("workload",
                                                    "list-append"),
                        realtime=bool(c.request.spec.get("realtime",
                                                         False)),
                        consistency_models=c.request.spec.get(
                            "consistency_models"),
                        budget_s=c.request.remaining_s())[0]
            except Exception as e2:  # noqa: BLE001
                r = {"valid": "unknown",
                     "error": f"device and host tiers both failed: "
                              f"{exc}; {e2}"}
            r.setdefault("fallback", {"from": f"{c.request.kind}-device",
                                      "to": "host", "error": str(exc),
                                      "error-type": type(exc).__name__})
            r["fallback-chain"] = chain
            out.append(r)
        return out

    def _finalize(self, cell: Cell, result: Dict[str, Any]) -> None:
        cell.result = result
        self.metrics.inc("cells-completed")
        req = cell.request
        if not req.claim_finish():
            return
        req.finish(aggregate(req))
        self.metrics.inc("requests-completed")
        self.metrics.trace(req)
