"""Fleet: a fault-tolerant multi-worker serving tier.

One :class:`CheckService` is still one scheduler thread on one device: a
wedged dispatch, a crashed loop, or one slow lane group takes the whole
service down.  The fleet runs N worker CheckServices (in-process
replicas today, one per host tomorrow — the submit surface is already
process-shaped), each pinned to its own slice of the host's devices,
behind a router that:

- hash-routes cells by key (rendezvous hashing, serve/router.py) so a
  key's repeat shapes keep hitting the same warm engine cache;
- health-checks workers (heartbeat thread + per-worker latency/error
  EWMAs) and circuit-breaks a failing one (open → half-open probe →
  close);
- retries and hedges deadline-risky cells onto siblings under a
  control/retry.py :class:`RetryPolicy` with decorrelated jitter (a
  worker death must not synchronize the survivors' retries into a
  storm);
- journals in-flight cells (atomic_io) so a crash — of a worker or of
  the whole fleet process — re-enqueues, never drops and never
  fabricates, its pending work.

Verdict discipline is the repo's: on every unrecoverable path the cell
degrades to ``valid: "unknown"``; a fleet failure can never produce a
``false`` the single-service oracle would not.  P-compositionality
(arXiv:1504.00204) is what makes all of this sound: cells are
independently-checkable units whose merge is associative, so a cell may
be retried, relocated, or hedged without changing any verdict.

The self-nemesis proof lives in serve/chaos.py +
scripts/fleet_chaos_smoke.py.
"""

from __future__ import annotations

import itertools
import json
import logging
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as FutureTimeout
from typing import Any, Callable, Dict, List, Optional, Tuple

from jepsen_tpu import atomic_io
from jepsen_tpu.control.retry import RetryPolicy
from jepsen_tpu.engine import ladder
from jepsen_tpu.net_proxy import PairProxy
from jepsen_tpu.history import History, Op
from jepsen_tpu.obs.hist import merge_hist_snapshots
from jepsen_tpu.obs.recorder import RECORDER
from jepsen_tpu.obs.slo import SloEngine, tenant_slo_specs
from jepsen_tpu.obs.telemetry import TelemetryStore, telemetry_interval_s
from jepsen_tpu.serve import fission_plane
from jepsen_tpu.serve.aggregate import aggregate, expired_result
from jepsen_tpu.serve.decompose import decompose
from jepsen_tpu.serve.metrics import Metrics, mono_now
from jepsen_tpu.serve.request import Cell, KIND_WGL, Request
from jepsen_tpu.serve.router import (
    CircuitBreaker, OPEN, Router, WorkerHealth,
)
from jepsen_tpu.serve.service import (
    CheckService, ServiceClosed, ServiceSaturated, build_spec,
    submit_kwargs,
)
from jepsen_tpu.serve.tenants import TenantTable

log = logging.getLogger("jepsen.serve.fleet")

#: completion-poll quantum while waiting on a worker-side request
POLL_S = 0.005
#: hedge trigger when a request carries no deadline
DEFAULT_HEDGE_S = 1.0
#: give-up bound for a no-deadline cell stuck on an unresponsive worker
NO_DEADLINE_WAIT_S = 120.0
#: default per-request budget — the fleet always runs with deadlines
#: unless the caller explicitly disables them (deadlines are what make
#: drop/delay faults recoverable instead of hangs)
DEFAULT_FLEET_DEADLINE_S = 60.0

#: worker-produced error strings that mean "the worker failed", not "the
#: history is undecidable" — these reroute to a sibling; every other
#: unknown is a legitimate verdict and is passed through.  Deliberately
#: narrow: retrying a budget-truncation unknown would loop forever.
_WORKER_FAILURE_ERRORS = (
    "scheduler dispatch crashed",
    "device and host tiers both failed",
    # transport.py's wire-failure verdicts: a lost/torn connection is a
    # worker(-link) failure by definition — the history never reached a
    # checker, so rerouting to a sibling is always sound
    "transport connection lost",
    "transport frame error",
)


#: floor of the per-worker lane ladder: a fleet worker never dispatches
#: narrower groups than this, however many siblings share the device.
MIN_WORKER_LANES = 8


def worker_lane_share(total_lanes: int, n_workers: int) -> int:
    """A fleet worker's per-dispatch lane budget when one device's lane
    allowance is split across N workers: ceil-divide, then round UP onto
    the power-of-two ladder (floor :data:`MIN_WORKER_LANES`).  Rounding
    up — not down — keeps every worker's dispatches on the same ladder
    rungs a solo service would use, so the fleet and the single-service
    oracle share compiled-engine cache entries instead of doubling the
    shape universe."""
    n = max(1, n_workers)
    share = (max(1, total_lanes) + n - 1) // n
    return min(ladder.MAX_LANE_BUCKET,
               ladder.pow2_at_least(share, MIN_WORKER_LANES))


def proc_worker_lanes(total_lanes: int, n_workers: int,
                      shared_host: bool = True) -> int:
    """A ProcFleet worker's per-dispatch lane budget.  Out-of-process
    workers on ONE host (today's shape: N subprocesses sharing the
    host's device) still split the device's lane allowance, so the
    budget divides exactly like :func:`worker_lane_share` — same ladder
    rungs, same shared compile cache with the solo oracle.  Workers that
    will land on their *own* hosts (``shared_host=False``, the
    multi-host direction) each take the full rung: nothing is shared,
    and dividing would just waste their private device."""
    if not shared_host:
        return worker_lane_share(total_lanes, 1)
    return worker_lane_share(total_lanes, n_workers)


def _device_sets(n: int) -> List[list]:
    """Partition the host's accelerators round-robin across N workers.
    Fewer devices than workers shares them (CPU CI: every worker pins
    the one CPU device); no jax at all degrades to unpinned."""
    try:
        import jax
        devs = list(jax.devices())
    except Exception:  # noqa: BLE001 — fleet works without a backend
        devs = []
    if not devs:
        return [[] for _ in range(n)]
    if len(devs) >= n:
        return [devs[i::n] for i in range(n)]
    return [[devs[i % len(devs)]] for i in range(n)]


class FleetWorker:
    """One worker slot: a CheckService plus its circuit, health, and
    device pin.  The slot survives its service — ``restart`` replaces
    the dead service in place, so the router's worker list stays
    index-stable across crashes."""

    def __init__(self, wid: int, make_service: Callable[[], CheckService],
                 devices: Optional[list] = None,
                 fail_threshold: int = 3, open_s: float = 1.0):
        self.wid = wid
        self.devices = devices or []
        self._make_service = make_service
        self.service = make_service()
        self.breaker = CircuitBreaker(fail_threshold=fail_threshold,
                                      open_s=open_s)
        self.health = WorkerHealth()
        self.generation = 0
        # scale-down lifecycle (serve/autoscale.py): a draining slot
        # takes no new cells (router filters it); a retired slot is dead
        # for good — the supervisor must not respawn it
        self.draining = False
        self.retired = False
        self._restart_lock = threading.Lock()

    def alive(self) -> bool:
        return self.service.alive()

    def fits(self, cell) -> bool:
        """Mesh/capability placement predicate for the router's ranked
        walk.  The base slot accepts every cell — today's fixed fleets
        are homogeneous; registry-backed slots (serve/fleetport.py)
        override this with the worker's advertised mesh capacity."""
        return True

    def kill(self) -> list:
        """Crash this worker (chaos fault / decommission): abrupt service
        kill, queued worker-side cells evicted.  The fleet's cell owners
        detect the death and reroute — nothing here touches fleet state."""
        return self.service.kill()

    def restart(self, only_if_dead: bool = False) -> bool:
        """Replace a dead service with a fresh one and reset the circuit
        (a restarted worker earns its traffic back through the normal
        closed-state accounting).  ``only_if_dead`` is the supervisor's
        guard — a chaos undo and the ProcFleet supervisor may both reach
        for the same corpse, and the restart lock plus the liveness
        re-check under it make exactly one of them actually respawn.
        Returns True iff THIS call replaced the service."""
        with self._restart_lock:
            if only_if_dead and self.alive():
                return False
            try:
                self.service.kill()
            except Exception:  # noqa: BLE001 — it's already dead
                pass
            self.service = self._make_service()
            self.generation += 1
            self.breaker.reset()
            return True

    def status(self) -> Dict[str, Any]:
        try:
            ping = self.service.ping()
        except Exception:  # noqa: BLE001
            ping = {"alive": False, "queue-depth": None,
                    "inflight-cells": None}
        return {"worker": self.wid,
                "alive": bool(ping.get("alive")),
                "circuit": self.breaker.state,
                "platform": ping.get("platform"),
                "queue-depth": ping.get("queue-depth"),
                "inflight-cells": ping.get("inflight-cells"),
                "generation": self.generation,
                "draining": self.draining,
                "retired": self.retired,
                "devices": [str(d) for d in self.devices],
                **self.health.snapshot()}


class FleetJournal:
    """The in-flight cell journal: an atomically-replaced JSON snapshot
    of every cell the fleet has admitted but not finished, durable
    through the atomic_io rename + directory-fsync discipline.  A fleet
    (or host) crash re-enqueues this file's cells on restart —
    :meth:`recover` — so admitted work is never silently dropped; a cell
    whose deadline budget is already spent is returned under
    ``expired``, explicitly, rather than re-checked against a deadline
    it can no longer meet.

    Format (``fleet-journal.json``)::

        {"version": 1,
         "pending": {"<cid>": {"request-id": int, "kind": "wgl"|"elle",
                               "key": ..., "deadline-rem-s": float|null,
                               "spec": {...build_spec kwargs, model by
                                        name...},
                               "ops": [history.jsonl op dicts]}}}
    """

    VERSION = 1
    FILENAME = "fleet-journal.json"
    #: the recovery-claim lock file (exclusive_create; single winner)
    CLAIMNAME = "fleet-journal.claim"

    def __init__(self, journal_dir: str):
        self.dir = atomic_io.durable_mkdir(journal_dir)
        self.path = os.path.join(self.dir, self.FILENAME)
        self._jlock = threading.Lock()    # pending-map mutations
        self._wlock = threading.Lock()    # one disk writer at a time
        self._pending: Dict[str, Dict[str, Any]] = {}
        self.writes = 0

    @staticmethod
    def _spec_lite(req: Request) -> Dict[str, Any]:
        spec = dict(req.spec)
        if req.kind == KIND_WGL:
            spec["model"] = spec["model"].name
        return spec

    def record(self, req: Request, cells: List[Cell]) -> None:
        entries = {}
        for c in cells:
            # fission children journal their per-cell spec overrides so
            # whole-fleet-crash recovery re-checks each sub-problem under
            # the exact engine options it scattered with (the group
            # context is gone — recovered children run as independent
            # requests, which the unknown-never-false table tolerates)
            entries[c.cid] = {
                "request-id": req.id, "kind": req.kind, "key": c.key,
                "deadline-rem-s": req.remaining_s(),
                "spec": {**self._spec_lite(req), **c.spec_overrides},
                "ops": [op.to_dict() for op in c.history]}
        with self._jlock:
            self._pending.update(entries)
        self._flush()

    def complete(self, cid: str) -> None:
        with self._jlock:
            self._pending.pop(cid, None)
        self._flush()

    def pending_count(self) -> int:
        with self._jlock:
            return len(self._pending)

    def _flush(self) -> None:
        # Snapshot INSIDE the writer lock: whoever writes, writes the
        # freshest state — a slow earlier writer can't clobber a newer
        # snapshot with a stale one.
        with self._wlock:
            with self._jlock:
                payload = {"version": self.VERSION,
                           "pending": dict(self._pending)}
            atomic_io.atomic_write(
                self.path,
                lambda f: json.dump(payload, f, default=str))
            self.writes += 1

    @classmethod
    def recover(cls, journal_dir: str) -> Dict[str, List[Dict[str, Any]]]:
        """Read a (possibly crashed) fleet's journal back into
        resubmittable work items: ``{"pending": [...], "expired":
        [...]}``, each item ``{"cid", "key", "history", "kwargs"}`` where
        ``kwargs`` feed :meth:`Fleet.submit` directly.  Entries whose
        deadline budget was already spent when journaled land in
        ``expired`` — recovery never invents deadline headroom."""
        path = os.path.join(journal_dir, cls.FILENAME)
        out: Dict[str, List[Dict[str, Any]]] = {"pending": [], "expired": []}
        if not os.path.exists(path):
            return out
        with open(path) as f:
            data = json.load(f)
        for cid, e in sorted(data.get("pending", {}).items()):
            spec = dict(e.get("spec") or {})
            kwargs = {"kind": e.get("kind", KIND_WGL), **spec}
            rem = e.get("deadline-rem-s")
            if rem is not None:
                kwargs["deadline_s"] = max(0.0, rem)
            item = {"cid": cid, "key": e.get("key"),
                    "history": History([Op.from_dict(d)
                                        for d in e.get("ops", [])]),
                    "kwargs": kwargs}
            if rem is not None and rem <= 0:
                out["expired"].append(item)
            else:
                out["pending"].append(item)
        return out

    # -- the recovery claim -----------------------------------------------
    # Two supervisors recovering the SAME journal directory (a respawned
    # fleet racing a slow-to-die predecessor, or an operator's manual
    # recovery racing an automatic one) would each resubmit every pending
    # cell: not a correctness bug (claim_finish dedups the verdict) but a
    # 2x re-check of every pending history.  The claim file — created
    # with O_CREAT|O_EXCL via atomic_io.exclusive_create — makes recovery
    # single-winner: exactly one claimant resubmits, the loser reports
    # who beat it.  A claim whose recorded pid is dead is STALE (the
    # claimant crashed mid-recovery) and may be stolen; the steal itself
    # races through os.replace, where again only one renamer wins.

    @staticmethod
    def _pid_alive(pid: Any) -> bool:
        try:
            pid = int(pid)
        except (TypeError, ValueError):
            return False
        if pid <= 0:
            # os.kill(0/-N, 0) signals whole process GROUPS — never probe
            return False
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            return False
        except OSError:
            return True  # e.g. EPERM: exists, not ours
        return True

    @classmethod
    def _claim_path(cls, journal_dir: str) -> str:
        return os.path.join(journal_dir, cls.CLAIMNAME)

    @classmethod
    def claim_holder(cls, journal_dir: str) -> Optional[Dict[str, Any]]:
        """The current claim record ({"claimant", "pid"}) or None."""
        try:
            with open(cls._claim_path(journal_dir)) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    @classmethod
    def claim_recovery(cls, journal_dir: str, claimant: str) -> bool:
        """Try to become THE recoverer of this journal directory.  True
        = we hold the claim (fresh win, our own re-claim, or a stale
        claim stolen); False = a live claimant beat us."""
        path = cls._claim_path(journal_dir)
        record = json.dumps({"claimant": claimant, "pid": os.getpid()})
        if atomic_io.exclusive_create(path, record):
            return True
        holder = cls.claim_holder(journal_dir)
        if holder is not None:
            if (holder.get("claimant") == claimant
                    and holder.get("pid") == os.getpid()):
                return True  # our own claim (idempotent re-claim)
            if cls._pid_alive(holder.get("pid")):
                return False
        # stale (dead pid) or unreadable: steal by renaming it aside —
        # os.replace is atomic, so of N stealers exactly one moves the
        # old claim and the rest lose the fresh exclusive_create below
        try:
            os.replace(path, path + ".stale")
        except FileNotFoundError:
            pass  # someone else already stole it; race them for the file
        except OSError:
            return False
        return atomic_io.exclusive_create(path, record)


class _FleetMetrics(Metrics):
    """The fleet's Metrics registry plus the fleet-wide scrape: a
    ``fleet`` snapshot section (per-worker status/circuits/journal), a
    ``workers`` section holding each worker's own ``Metrics.snapshot()``
    (fetched over the STATUS frame for out-of-process workers,
    best-effort — a partitioned worker scrapes as ``unreachable``, it
    never fails the document), and a ``histograms`` section that merges
    the fleet's own histograms with every reachable worker's, bucket by
    bucket (the pow2 ladders are identical in every process) — web.py's
    ``/metrics`` payload keeps one schema whether a CheckService, a
    Fleet, or a ProcFleet is attached."""

    def __init__(self, fleet: "Fleet"):
        super().__init__()
        self._fleet = fleet

    def snapshot(self) -> Dict[str, Any]:
        snap = super().snapshot()
        snap["fleet"] = self._fleet.fleet_status()
        worker_snaps = self._fleet.worker_snapshots()
        snap["histograms"] = merge_hist_snapshots(
            [snap.get("histograms")]
            + [(w or {}).get("histograms") for w in worker_snaps])
        workers = []
        for i, w in enumerate(worker_snaps):
            if w is None:
                workers.append({"worker": i, "unreachable": True})
                continue
            # traces stay fleet-side (the merged tree already absorbed
            # the worker spans); per-worker entries keep the numbers
            entry = {k: v for k, v in w.items()
                     if k not in ("traces", "fleet", "workers")}
            workers.append({"worker": i, **entry})
        snap["workers"] = workers
        # Watchtower sections (guarded: a snapshot taken while the fleet
        # is still constructing must not crash on the missing store)
        tele = getattr(self._fleet, "telemetry", None)
        if tele is not None:
            snap["telemetry"] = tele.snapshot()
        slo = getattr(self._fleet, "slo", None)
        if slo is not None:
            snap["slo"] = slo.snapshot()
        gov = getattr(self._fleet, "governor", None)
        if gov is not None:
            snap["autoscale"] = gov.snapshot()
        return snap


class Fleet:
    """N worker CheckServices behind a router — the CheckService facade
    (submit/check/try_route_analyze/metrics/close) at fleet scale, so
    ``test["service"]``, the web front end, and the CLI take a Fleet
    anywhere they take a service."""

    def __init__(self, workers: int = 3, *,
                 journal_dir: Optional[str] = None,
                 max_lanes: int = 64,
                 max_queue_cells: int = 4096,
                 default_deadline_s: Optional[float]
                 = DEFAULT_FLEET_DEADLINE_S,
                 mesh=None,
                 capacity: Optional[int] = None,
                 max_capacity: int = 65536,
                 hedge_s: Optional[float] = None,
                 heartbeat_s: float = 0.25,
                 retry_policy: Optional[RetryPolicy] = None,
                 breaker_fail_threshold: int = 3,
                 breaker_open_s: float = 1.0,
                 pin_devices: bool = True,
                 telemetry_s: Optional[float] = None):
        n = max(1, int(workers))
        self.n_workers = n
        self.max_lanes = max_lanes
        self.max_queue_cells = max_queue_cells
        self.default_deadline_s = default_deadline_s
        self.hedge_s = hedge_s
        self.heartbeat_s = heartbeat_s
        # resolved before _make_workers: proc slots ship the cadence to
        # their worker processes as a --telemetry-s argv flag
        self.telemetry_s = (telemetry_interval_s() if telemetry_s is None
                            else float(telemetry_s))
        self._t0 = mono_now()
        device_sets = _device_sets(n) if pin_devices else [[]] * n
        self.workers: List[FleetWorker] = self._make_workers(
            n, worker_lane_share(max_lanes, n), device_sets,
            mesh=mesh, capacity=capacity, max_capacity=max_capacity,
            fail_threshold=breaker_fail_threshold,
            open_s=breaker_open_s)
        self.router = Router(self.workers)
        self.metrics = _FleetMetrics(self)
        # Watchtower: the per-worker push ring + the SLO engine over it.
        # Proc workers push TELEMETRY frames into _note_worker_telemetry;
        # in-process workers (no wire) are scraped into the same store on
        # the heartbeat cadence, and the fleet process contributes its
        # own base snapshot as the "fleet" pseudo-worker.
        # Spawned workers spend real wall time booting before their
        # first push can exist; the fleet's ready timeout doubles as the
        # never-pushed staleness grace (ProcFleet sets it before calling
        # up here; in-process fleets have no boot gap and get none).
        self.telemetry = TelemetryStore(
            interval_s=self.telemetry_s if self.telemetry_s > 0 else None,
            startup_grace_s=getattr(self, "worker_ready_timeout_s", 0.0))
        self.slo = SloEngine(self.telemetry)
        for w in self.workers:
            self.telemetry.register(w.wid)
        self.telemetry.register("fleet")
        self._last_tele_sweep = 0.0
        # Multi-tenant QoS (serve/tenants.py): quotas/priorities from
        # JEPSEN_TPU_TENANT_*; tenants with configured SLO ceilings get
        # their own burn specs over the fleet pseudo-worker's pushes.
        self.tenants = TenantTable.from_env()
        for spec in tenant_slo_specs(self.tenants.slo_config(),
                                     self.telemetry_s):
            self.slo.add_spec(spec)
        # the Governor (serve/autoscale.py) attaches itself here so the
        # metrics snapshot can carry its decision ring
        self.governor = None
        # Decorrelated jitter by default: reroutes after a worker death
        # must not arrive at the survivor in lockstep (retry storm).
        self.retry_policy = retry_policy or RetryPolicy(
            tries=4, backoff_s=0.02, max_backoff_s=0.5, decorrelated=True)
        self._journal = (FleetJournal(journal_dir)
                         if journal_dir else None)
        self._pool = ThreadPoolExecutor(
            max_workers=max(8, 4 * n), thread_name_prefix="fleet-cell")
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._open_cells: Dict[str, Cell] = {}
        self._cids = itertools.count(1)
        self._submitted = 0
        self._closed = False
        self.metrics.bind(self.queue_depth, self._inflight)
        self.metrics.bind_queue(self.queue_occupancy)
        self.metrics.bind_tenants(self.tenants.counts)
        self._hb_thread = threading.Thread(
            target=self._heartbeat_loop, daemon=True,
            name="fleet-heartbeat")
        self._hb_thread.start()

    def _make_workers(self, n: int, lanes_each: int,
                      device_sets: List[list], *, mesh,
                      capacity: Optional[int], max_capacity: int,
                      fail_threshold: int,
                      open_s: float) -> List["FleetWorker"]:
        """Build the worker slots — ProcFleet overrides this to put each
        slot's service behind the wire instead of in-process."""

        def make_service(i: int) -> Callable[[], CheckService]:
            devs = device_sets[i] if i < len(device_sets) else []

            def make() -> CheckService:
                return CheckService(
                    max_queue_cells=self.max_queue_cells,
                    max_lanes=lanes_each, mesh=mesh,
                    capacity=capacity, max_capacity=max_capacity,
                    device=devs[0] if devs else None)
            return make

        # kept for scale-up (add_worker): a slot built past the initial
        # N runs unpinned — on CPU CI that is every slot anyway, and a
        # scaled-up accelerator slot sharing device 0 still adds queue
        # capacity and host-tier throughput
        self._slot_factory = lambda wid: FleetWorker(
            wid, make_service(wid),
            devices=device_sets[wid] if wid < len(device_sets) else [],
            fail_threshold=fail_threshold, open_s=open_s)
        return [self._slot_factory(i) for i in range(n)]

    # -- submission -------------------------------------------------------
    def _inflight(self) -> int:
        snap = self.metrics._counters
        return max(0, self._submitted
                   - snap.get("requests-completed", 0))

    def submit(self, history: History, *,
               kind: str = KIND_WGL,
               deadline_s: Optional[float] = None,
               block: bool = True,
               timeout: Optional[float] = None,
               trace: Optional[Dict[str, Any]] = None,
               tenant: Optional[str] = None,
               **kw) -> Request:
        """Enqueue one history check across the fleet; same contract as
        CheckService.submit, including the admission-race rule: a request
        whose deadline expires while blocked on admission — its tenant's
        quota or fleet backpressure — resolves ``unknown`` — never
        dropped, never false.  ``trace`` and ``tenant`` ride beside the
        spec (never inside it — reroute and journal recovery round-trip
        the spec through build_spec)."""
        if self._is_closed():
            raise ServiceClosed("fleet is closed")
        spec = build_spec(kind, **kw)
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        req = Request(history, kind, spec, deadline_s=deadline_s,
                      trace=trace, tenant=tenant,
                      priority=self.tenants.priority(tenant))
        cells = decompose(req)
        # Hydra: over-threshold WGL cells scatter into fission child
        # cells HERE, before admission/journaling, so backpressure,
        # quotas, the journal, and the router all see the real
        # per-sub-problem work (serve/fission_plane.py)
        cells = fission_plane.scatter(req)
        for c in cells:
            c.cid = f"{req.id}.{next(self._cids)}"
        adm_deadline = req.deadline
        if timeout is not None:
            t_lim = mono_now() + timeout
            adm_deadline = t_lim if adm_deadline is None \
                else min(adm_deadline, t_lim)
        if not self.tenants.acquire(tenant, block=block,
                                    deadline=adm_deadline):
            if req.expired():
                return self._finish_expired(req, cells)
            self.metrics.inc("requests-rejected")
            raise ServiceSaturated(
                f"tenant {tenant!r} at quota; request of "
                f"{len(cells)} cell(s) rejected")
        req.on_finish = lambda t=tenant: self.tenants.release(t)
        if not self._admit(cells, block=block, timeout=timeout):
            if req.expired():
                return self._finish_expired(req, cells)
            self.tenants.release(tenant)
            req.on_finish = None
            self.metrics.inc("requests-rejected")
            raise ServiceSaturated(
                f"fleet at {self.queue_depth()}/{self.max_queue_cells} "
                f"open cells; request of {len(cells)} cell(s) rejected")
        self._count_submit(len(cells))
        if self._journal is not None:
            self._journal.record(req, cells)
        for c in cells:
            self._pool.submit(self._run_cell, c)
        return req

    def _count_submit(self, n_cells: int) -> None:
        with self._lock:
            self._submitted += 1
        self.metrics.inc("requests-submitted")
        self.metrics.inc("cells-submitted", n_cells)

    def _finish_expired(self, req: Request, cells: List[Cell]) -> Request:
        """Expiry-while-blocked (quota or backpressure): every cell
        resolves unknown and the handle comes back already done."""
        for c in cells:
            c.result = expired_result(req.kind)
        self.metrics.inc("deadline-expired", len(cells))
        self._count_submit(len(cells))
        self.metrics.inc("cells-completed", len(cells))
        self.metrics.inc("requests-completed")
        req.finish(aggregate(req))
        self.metrics.trace(req)
        return req

    def _admit(self, cells: List[Cell], block: bool,
               timeout: Optional[float]) -> bool:
        """Fleet-tier backpressure: all-or-nothing admission against the
        fleet-wide open-cell count, bounded by the request deadline."""
        req = cells[0].request
        deadline = None
        if timeout is not None:
            deadline = mono_now() + timeout
        rem = req.remaining_s()
        if rem is not None:
            d = mono_now() + rem
            deadline = d if deadline is None else min(deadline, d)
        with self._cond:
            while (not self._closed
                   and len(self._open_cells) + len(cells)
                   > self.max_queue_cells):
                if not block:
                    return False
                left = None if deadline is None else deadline - mono_now()
                if left is not None and left <= 0:
                    return False
                if not self._cond.wait(timeout=left if left is not None
                                       else 0.1):
                    return False
            if self._closed:
                raise ServiceClosed("fleet is closed")
            for c in cells:
                self._open_cells[c.cid] = c
            return True

    def check(self, history: History, *, timeout: Optional[float] = None,
              **kw) -> Dict[str, Any]:
        return self.submit(history, **kw).wait(timeout=timeout)

    # -- the per-cell driver ---------------------------------------------
    def _run_cell(self, cell: Cell, exclude: Tuple[int, ...] = ()) -> None:
        """One owner thread drives one cell to a verdict: route, wait,
        hedge, reroute, and finally — on every path — finalize.  The cell
        can end unresolved only if this thread dies, so the body is one
        try/except that degrades to unknown."""
        try:
            result = self._drive_cell(cell, exclude)
        except Exception as e:  # noqa: BLE001 — a driver bug must not
            log.exception("fleet cell driver crashed for %s", cell.cid)
            result = {"valid": "unknown", "analyzer": "fleet",
                      "error": f"fleet cell driver crashed: {e}"}
        self._finalize_cell(cell, result)

    def _drive_cell(self, cell: Cell,
                    exclude: Tuple[int, ...]) -> Dict[str, Any]:
        req = cell.request
        policy = self.retry_policy
        token = cell.route_token()
        excluded = set(exclude)
        attempts: List[Dict[str, Any]] = []
        prev_delay: Optional[float] = None
        tries = max(1, policy.tries)
        for attempt in range(tries):
            if cell.cancelled:
                return fission_plane.cancelled_result()
            if req.expired():
                self.metrics.inc("deadline-expired")
                return expired_result(req.kind)
            worker = self.router.pick(token, exclude=excluded, cell=cell)
            if worker is None:
                # Every alive worker's circuit is open (or everyone is
                # dead).  Wait out a cooldown — a half-open probe slot
                # may appear — then retry against the full fleet.
                self.metrics.inc("no-worker-available")
                attempts.append({"worker": None,
                                 "error": "no routable worker"})
                if attempt + 1 >= tries:
                    break
                prev_delay = policy.delay(attempt, prev=prev_delay)
                self._sleep_bounded(prev_delay, req)
                excluded = set(exclude)
                continue
            t0 = mono_now()
            res, failure, offender = self._attempt_on(worker, cell)
            took = mono_now() - t0
            offender = offender or worker
            if not failure:
                offender.breaker.record_success()
                offender.health.observe(latency_s=took)
                if res is None:  # pure expiry surfaced by the wait loop
                    res = expired_result(req.kind)
                res.setdefault("fleet", {})
                res["fleet"].update({"worker": offender.wid,
                                     "attempts": attempt + 1,
                                     "rerouted": attempt > 0})
                return res
            offender.breaker.record_failure()
            offender.health.observe(latency_s=took, error=True)
            self.metrics.inc("worker-failures")
            attempts.append({"worker": offender.wid, "error": failure})
            excluded.add(offender.wid)
            if len(excluded) >= len(self.workers_snapshot()):
                # Everyone has failed this cell once; a retry round
                # against recovered/restarted workers is still worth it.
                excluded = set(exclude)
            if attempt + 1 < tries:
                self.metrics.inc("cells-rerouted")
                RECORDER.record(
                    "retry", f"reroute:{cell.cid}", trace_id=req.trace_id,
                    span_id=req.span_id,
                    args={"attempt": attempt + 1, "worker": offender.wid,
                          "error": (failure or "")[:160]})
                prev_delay = policy.delay(attempt, prev=prev_delay)
                self._sleep_bounded(prev_delay, req)
        if req.expired():
            self.metrics.inc("deadline-expired")
            return expired_result(req.kind)
        return {"valid": "unknown", "analyzer": "fleet",
                "error": f"all {tries} fleet attempts failed",
                "fleet": {"attempts-log": attempts}}

    def _attempt_on(self, worker: FleetWorker,
                    cell: Cell) -> Tuple[Optional[Dict[str, Any]],
                                         Optional[str],
                                         Optional[FleetWorker]]:
        """One routed attempt: submit the cell to ``worker`` and wait,
        hedging to a sibling when the wait turns deadline-risky.  Returns
        ``(result, failure_reason, worker_of_record)``: ``failure_reason``
        is None on success (including a legitimate unknown) and a string
        when a worker — not the history — failed; ``worker_of_record`` is
        whoever actually produced the outcome (the hedge sibling when the
        hedge won), so the caller credits/penalizes the right breaker.  A
        hedge that lands on a broken sibling is penalized HERE and
        dropped — the still-running primary attempt is not abandoned for
        a sibling's failure."""
        req = cell.request
        # fleet-side dispatch mark: edge:dispatch->verdict in THIS
        # process's histograms is the full wire round trip + worker
        # time — the latency an injected slow link actually inflates
        # (worker-side spans never see the network)
        req.span("dispatch")
        try:
            wreq = worker.service.submit(cell.history, block=False,
                                         deadline_s=req.remaining_s(),
                                         trace=req.trace_context(),
                                         **self._cell_kwargs(cell))
        except (ServiceClosed, ServiceSaturated) as e:
            return None, f"{type(e).__name__}: {e}", worker
        except Exception as e:  # noqa: BLE001 — submit crashed = worker bug
            return None, f"submit crashed: {type(e).__name__}: {e}", worker
        hedge_at = self._hedge_after(req)
        hreq = None
        hedge_worker: Optional[FleetWorker] = None
        hedge_excluded = {worker.wid}
        t0 = mono_now()
        cap = req.remaining_s()
        cap = NO_DEADLINE_WAIT_S if cap is None else cap
        while True:
            if wreq.done():
                # a completed hedge loser still contributed spans — keep
                # them in the tree before abandoning the handle
                if hreq is not None and hreq.done():
                    req.absorb_serve(hreq.result)
                res, failure = self._classify(dict(wreq.result or {}), req)
                return res, failure, worker
            if hreq is not None and hreq.done():
                res, failure = self._classify(dict(hreq.result or {}), req)
                if failure:
                    req.absorb_serve(hreq.result)  # keep the failed
                    # sibling's spans — the trace shows the attempt
                    # The hedge landed on a broken sibling: penalize IT,
                    # drop the hedge, keep waiting on the primary (whose
                    # attempt is still live and may well succeed).
                    hedge_worker.breaker.record_failure()
                    hedge_worker.health.observe(error=True)
                    self.metrics.inc("worker-failures")
                    hedge_excluded.add(hedge_worker.wid)
                    hreq = None
                    hedge_worker = None
                    hedge_at = (mono_now() - t0) + 0.1
                else:
                    self.metrics.inc("hedge-wins")
                    if res is not None:
                        res.setdefault("fleet", {})["hedged-from"] = \
                            worker.wid
                    return res, None, hedge_worker
            now = mono_now()
            if cell.cancelled:
                # a sibling decided this cell's fission group; the worker
                # keeps computing (never interrupted) but its verdict no
                # longer matters — release the driver thread now
                return fission_plane.cancelled_result(), None, worker
            if req.expired():
                return None, None, worker  # pure expiry → unknown upstream
            if now - t0 > cap:
                return None, "worker unresponsive past wait cap", worker
            if not worker.alive() and (hreq is None
                                       or (hedge_worker is not None
                                           and not hedge_worker.alive())):
                return None, "worker died mid-check", worker
            if hreq is None and hedge_at is not None \
                    and now - t0 >= hedge_at:
                hedge_worker = self.router.pick(cell.route_token(),
                                                exclude=hedge_excluded,
                                                cell=cell)
                if hedge_worker is not None:
                    try:
                        hreq = hedge_worker.service.submit(
                            cell.history, block=False,
                            deadline_s=req.remaining_s(),
                            trace=req.trace_context(),
                            **self._cell_kwargs(cell))
                        self.metrics.inc("hedges")
                        RECORDER.record(
                            "retry", f"hedge:{cell.cid}",
                            trace_id=req.trace_id, span_id=req.span_id,
                            args={"primary": worker.wid,
                                  "hedge": hedge_worker.wid})
                    except Exception:  # noqa: BLE001 — sibling saturated
                        hreq = None
                        hedge_worker = None
                if hreq is None:
                    # No sibling available; re-arm the hedge for later.
                    hedge_at = (now - t0) + max(0.1, self._hedge_after(req)
                                                or DEFAULT_HEDGE_S)
            time.sleep(POLL_S)

    def _cell_kwargs(self, cell: Cell) -> Dict[str, Any]:
        """The worker submit kwargs for one cell: the request spec with
        the cell's fission overrides merged over it (ghost-variant
        children pin worker fission off and a threshold-sized ceiling;
        ordinary cells have no overrides and this IS submit_kwargs)."""
        kw = submit_kwargs(cell.request)
        kw.update(cell.spec_overrides)
        return kw

    def _classify(self, res: Dict[str, Any],
                  req: Request) -> Tuple[Optional[Dict[str, Any]],
                                         Optional[str]]:
        """Worker failure vs legitimate verdict.  Narrow on purpose: only
        error strings the scheduler emits when *it* (not the history)
        failed count as retriable — rerouting a budget-truncation or
        deadline unknown would re-check forever."""
        err = str(res.get("error") or "")
        if res.get("valid") == "unknown" and not req.expired() \
                and any(err.startswith(m) for m in _WORKER_FAILURE_ERRORS):
            return None, f"worker-tier failure: {err}"
        return res, None

    def _hedge_after(self, req: Request) -> Optional[float]:
        """When to fire the hedge: the configured knob, else half the
        remaining budget clamped to [50 ms, 2 s] (a late hedge is a
        useless hedge), else the no-deadline default."""
        if self.hedge_s is not None:
            return self.hedge_s
        rem = req.remaining_s()
        if rem is None:
            return DEFAULT_HEDGE_S
        return min(2.0, max(0.05, rem * 0.5))

    def _sleep_bounded(self, d: float, req: Request) -> None:
        """Backoff that never sleeps through the deadline."""
        rem = req.remaining_s()
        if rem is not None:
            d = max(0.0, min(d, rem))
        if d > 0:
            time.sleep(d)

    def _finalize_cell(self, cell: Cell, result: Dict[str, Any]) -> None:
        # Hydra's evidence seam: fission children get witness recovery
        # (pinned to the refuting worker) and trigger sibling cancel
        # before the verdict is committed; ordinary cells pass through.
        try:
            result = fission_plane.on_child_result(self, cell, result)
        except Exception as e:  # noqa: BLE001 — the seam must never lose
            log.exception("fission finalize seam failed for %s", cell.cid)
            result = {"valid": "unknown", "analyzer": "fleet-fission",
                      "error": f"fission finalize seam crashed: {e}"}
        cell.result = result
        self.metrics.inc("cells-completed")
        req = cell.request
        # fold the winning attempt's worker-side spans into the root's
        # tree before aggregation buries them under per-key results
        req.absorb_serve(result)
        if req.claim_finish():
            req.finish(aggregate(req))
            self.metrics.inc("requests-completed")
            self.metrics.trace(req)
        if self._journal is not None:
            self._journal.complete(cell.cid)
        with self._cond:
            self._open_cells.pop(cell.cid, None)
            self._cond.notify_all()

    # -- health -----------------------------------------------------------
    def _heartbeat_loop(self) -> None:
        while not self._is_closed():
            for w in self.workers_snapshot():
                if w.retired:
                    continue  # decommissioned slot: dead for good
                try:
                    p = w.service.ping()
                except Exception:  # noqa: BLE001
                    p = {"alive": False}
                w.health.beat()
                self.telemetry.observe_breaker(w.wid,
                                               w.breaker.state == OPEN)
                if not p.get("alive"):
                    self.metrics.inc("heartbeat-misses")
            try:
                self._telemetry_sweep()
            except Exception:  # noqa: BLE001 — telemetry must never
                log.exception("telemetry sweep failed")  # kill heartbeat
            time.sleep(self.heartbeat_s)

    # -- Watchtower -------------------------------------------------------
    def _note_worker_telemetry(self, wid: int,
                               payload: Dict[str, Any]) -> None:
        """Sink for one proc worker's TELEMETRY push (runs on that
        worker's wire reader thread).  Tags the slot's generation —
        worker processes don't know which respawn they are — then lands
        the push and evaluates the SLOs against it."""
        try:
            w = self.workers_snapshot()[wid]
        except (IndexError, TypeError):
            return
        payload = dict(payload or {})
        payload.setdefault("generation", w.generation)
        self.telemetry.record_push(wid, payload)
        self.slo.evaluate(wid)

    def _telemetry_sweep(self) -> None:
        """Heartbeat-cadence half of the telemetry plane: once per push
        interval, contribute the fleet process's own base snapshot as
        the ``fleet`` pseudo-worker, scrape in-process (wireless) worker
        services into the store, and run one SLO sweep over everyone —
        the sweep is what catches staleness, since a stale worker by
        definition sends no push to evaluate."""
        if self.telemetry_s <= 0:
            return
        now = mono_now()
        if now - self._last_tele_sweep < self.telemetry.interval_s:
            return
        self._last_tele_sweep = now
        snap = Metrics.snapshot(self.metrics)  # base sections only — the
        snap.pop("traces", None)               # full fleet snapshot would
        # re-scrape every worker per interval
        self.telemetry.record_push("fleet", {
            "pid": os.getpid(),
            "uptime-s": round(now - self._t0, 3),
            "interval-s": self.telemetry.interval_s,
            "metrics": snap}, now=now)
        for w in self.workers_snapshot():
            svc = w.service
            if w.retired:
                continue  # evicted from the store; must not re-register
            if hasattr(svc, "metrics_snapshot"):
                continue  # wire-backed: its process pushes for itself
            m = getattr(svc, "metrics", None)
            if m is None:
                continue
            try:
                ws = dict(m.snapshot())
            except Exception:  # noqa: BLE001 — mid-crash worker
                continue
            ws.pop("traces", None)
            self.telemetry.record_push(w.wid, {
                "pid": os.getpid(), "generation": w.generation,
                "interval-s": self.telemetry.interval_s,
                "metrics": ws}, now=now)
        self.slo.evaluate_all(now=now)

    def alerts(self) -> List[Dict[str, Any]]:
        """The SLO engine's fired-alert ring (web.py GET /alerts)."""
        return self.slo.alerts()

    def set_recorder(self, on: bool) -> Dict[str, Any]:
        """Arm/disarm the flight recorder at runtime — locally and, for
        wire-backed workers, remotely over the STATUS frame (POST
        /recorder).  Best-effort per worker; returns who acked."""
        if on:
            RECORDER.enable()
        else:
            RECORDER.disable()
        acks: Dict[str, bool] = {}
        for w in self.workers_snapshot():
            fn = getattr(w.service, "set_recorder", None)
            if fn is None:
                continue   # in-process worker: shares this RECORDER
            try:
                acks[str(w.wid)] = bool(fn(on))
            except Exception:  # noqa: BLE001 — unreachable worker
                acks[str(w.wid)] = False
        return {"enabled": RECORDER.enabled, "workers": acks,
                **RECORDER.stats()}

    def restart_worker(self, wid: int,
                       only_if_dead: bool = False) -> FleetWorker:
        """Bring a (dead) worker slot back with a fresh service; its
        journal-relevant state lives fleet-side, so nothing is replayed
        here — cells routed to the corpse already rerouted via their
        owner threads."""
        w = self.workers_snapshot()[wid]
        if w.restart(only_if_dead=only_if_dead):
            self.metrics.inc("worker-restarts")
        return w

    # -- Governor scale plane (serve/autoscale.py) ------------------------
    def can_scale_locally(self) -> bool:
        """Can this fleet spawn a worker slot in-process?  ProcFleet and
        registry-backed fleets answer False — the Governor emits a
        structured scale request for the deployment layer instead."""
        return getattr(self, "_slot_factory", None) is not None

    def active_workers(self) -> int:
        """Slots currently able to take traffic: alive, not draining,
        not retired — the autoscaler's worker-count signal."""
        return sum(1 for w in self.workers_snapshot()
                   if w.alive() and not w.draining and not w.retired)

    def journal_pending(self) -> int:
        return self._journal.pending_count() if self._journal else 0

    def queue_occupancy(self) -> Dict[str, Any]:
        """Fleet-tier occupancy: open cells by bucket plus the oldest
        open request's wait-age — the same shape CheckService exposes
        from its scheduler, so the autoscaler (and the prom rendering)
        read one schema at either tier."""
        now = mono_now()
        with self._lock:
            cells = list(self._open_cells.values())
        buckets_out: Dict[str, int] = {}
        oldest = 0.0
        for c in cells:
            b = str(c.bucket)
            buckets_out[b] = buckets_out.get(b, 0) + 1
            oldest = max(oldest, now - c.request.submitted)
        return {"depth": len(cells), "buckets": buckets_out,
                "oldest-wait-s": round(oldest, 6)}

    def add_worker(self) -> FleetWorker:
        """Scale up: append one fresh worker slot.  The router shares the
        live worker list, so the new slot starts taking rendezvous
        traffic immediately; its wid is append-only (never reused) to
        keep journal records and telemetry history unambiguous."""
        if not self.can_scale_locally():
            raise RuntimeError("fleet cannot spawn worker slots locally; "
                               "consume the Governor's scale requests "
                               "instead")
        with self._lock:
            if self._closed:
                raise ServiceClosed("fleet is closed")
            wid = len(self.workers)
            w = self._slot_factory(wid)
            self.workers.append(w)
            self.n_workers = len(self.workers)
        self.telemetry.register(w.wid)
        self.metrics.inc("workers-added")
        return w

    def decommission_worker(self, wid: int,
                            timeout_s: float = 30.0) -> Dict[str, Any]:
        """Scale down strictly by lease drain: mark the slot draining
        (the router stops ranking it), wait until it is idle AND the
        journal has zero pending cells, then retire and kill it.  A
        drain that cannot complete within ``timeout_s`` ABORTS — the
        slot un-drains and keeps serving, because killing a worker with
        journal-pending work would turn bounded unknowns into recovery
        churn.  Returns the decision evidence either way."""
        w = self.workers_snapshot()[wid]
        w.draining = True
        deadline = mono_now() + timeout_s
        drained = False
        while mono_now() < deadline and not self._is_closed():
            try:
                p = w.service.ping()
            except Exception:  # noqa: BLE001 — already dead is idle
                p = {"alive": False, "queue-depth": 0, "inflight-cells": 0}
            idle = (not p.get("alive")
                    or (p.get("queue-depth") == 0
                        and p.get("inflight-cells") == 0))
            if idle and self.journal_pending() == 0:
                drained = True
                break
            time.sleep(0.05)
        pending = self.journal_pending()
        if not drained:
            w.draining = False
            self.metrics.inc("decommission-aborts")
            return {"worker": wid, "drained": False,
                    "journal-pending": pending}
        w.retired = True
        try:
            w.kill()
        except Exception:  # noqa: BLE001 — racing a chaos kill is fine
            pass
        self.slo.forget(wid)
        self.telemetry.evict(wid)
        self.metrics.inc("workers-decommissioned")
        return {"worker": wid, "drained": True, "journal-pending": pending}

    def fleet_status(self) -> Dict[str, Any]:
        workers = self.workers_snapshot()
        return {"workers": [w.status() for w in workers],
                "journal": {"enabled": self._journal is not None,
                            "pending": (self._journal.pending_count()
                                        if self._journal else 0),
                            "writes": (self._journal.writes
                                       if self._journal else 0),
                            "path": (self._journal.path
                                     if self._journal else None)},
                "circuits": {w.wid: dict(w.breaker.transitions)
                             for w in workers}}

    def worker_snapshots(self) -> List[Optional[Dict[str, Any]]]:
        """Scrape every worker's ``Metrics.snapshot()`` — for in-process
        workers straight off the service, for proc workers over the
        STATUS frame (``metrics_snapshot``).  Best-effort per worker: a
        partitioned or dead worker contributes ``None``, never an
        exception — one bad link must not fail the fleet's /metrics
        document."""
        out: List[Optional[Dict[str, Any]]] = []
        for w in self.workers_snapshot():
            snap: Optional[Dict[str, Any]] = None
            try:
                svc = w.service
                ms = getattr(svc, "metrics_snapshot", None)
                if ms is not None:          # ProcWorkerService: over STATUS
                    snap = ms()
                else:
                    m = getattr(svc, "metrics", None)
                    if m is not None:
                        snap = m.snapshot()
            except Exception:  # noqa: BLE001 — a scrape never fails the doc
                snap = None
            out.append(snap)
        return out

    def merged_trace(self, request_id: str) -> Optional[Dict[str, Any]]:
        """The fully-assembled causal tree for a finished request: root
        spans from this fleet process plus every worker/hedge subtree
        absorbed off RESULT frames (see Request.absorb_serve)."""
        return self.metrics.find_trace(request_id)

    #: per-probe wall bound on the whole deep-healthz fan-out — one hung
    #: worker must cost the endpoint at most this, not its rpc timeout
    #: serially multiplied by the fleet size.  Env-overridable
    #: (JEPSEN_TPU_DEEP_HEALTHZ_S): a WAN-hop worker in a multi-host
    #: fleet cannot answer inside the loopback-tuned 2 s window.
    DEEP_HEALTHZ_TIMEOUT_S = 2.0

    @classmethod
    def deep_healthz_timeout_s(cls) -> float:
        """The deep-healthz fan-out budget: ``JEPSEN_TPU_DEEP_HEALTHZ_S``
        (seconds, > 0) or the 2 s default.  Read at call time so a
        running fleet picks up a re-tune without restart."""
        raw = os.environ.get("JEPSEN_TPU_DEEP_HEALTHZ_S", "")
        try:
            v = float(raw) if raw else cls.DEEP_HEALTHZ_TIMEOUT_S
        except ValueError:
            return cls.DEEP_HEALTHZ_TIMEOUT_S
        return v if v > 0 else cls.DEEP_HEALTHZ_TIMEOUT_S

    def healthz(self, deep: bool = False,
                deep_timeout_s: Optional[float] = None) -> Dict[str, Any]:
        """The load-balancer/chaos probe payload (web.py GET /healthz):
        fleet is ``ok`` while at least one worker is alive with a
        non-open circuit.  ``deep`` additionally asks each remote worker
        for its OWN healthz over the wire (``GET /healthz?deep=1``) —
        fanned out in parallel with one shared wall bound, so a single
        hung or partitioned worker degrades ITS entry to a timeout
        error instead of stalling the whole endpoint behind its RPC."""
        st = self.fleet_status()
        ok = any(w["alive"] and w["circuit"] != OPEN
                 for w in st["workers"])
        if deep:
            budget = (self.deep_healthz_timeout_s()
                      if deep_timeout_s is None else float(deep_timeout_s))
            targets = [(w, entry)
                       for w, entry in zip(self.workers_snapshot(),
                                           st["workers"])
                       if getattr(w.service, "healthz", None) is not None]
            if targets:
                pool = ThreadPoolExecutor(
                    max_workers=len(targets),
                    thread_name_prefix="fleet-deepz")
                futs = [(pool.submit(w.service.healthz), entry)
                        for w, entry in targets]
                deadline = mono_now() + budget
                for fut, entry in futs:
                    try:
                        entry["remote"] = fut.result(
                            timeout=max(0.0, deadline - mono_now()))
                    except FutureTimeout:
                        fut.cancel()
                        entry["remote"] = {
                            "ok": False,
                            "error": f"deep healthz timeout after "
                                     f"{budget:.2f}s"}
                    except Exception as e:  # noqa: BLE001 — bad link
                        entry["remote"] = {
                            "ok": False,
                            "error": f"{type(e).__name__}: {e}"}
                # never wait on stragglers: a hung probe thread is
                # abandoned to finish (or not) on its own
                pool.shutdown(wait=False)
        return {"ok": ok, "queue-depth": self.queue_depth(), **st}

    # -- journal recovery -------------------------------------------------
    @staticmethod
    def recover(journal_dir: str) -> Dict[str, List[Dict[str, Any]]]:
        """Read a crashed fleet's journal: see FleetJournal.recover."""
        return FleetJournal.recover(journal_dir)

    def resubmit_recovered(self, journal_dir: str,
                           claimant: Optional[str] = None
                           ) -> Dict[str, Any]:
        """Re-enqueue a crashed fleet's journaled cells onto THIS fleet.
        Pending cells are resubmitted with their remaining deadline
        budget; already-expired cells are NOT re-checked — they are
        reported so the caller can surface their ``unknown`` explicitly.

        Recovery is single-winner: the claim file (exclusive_create,
        stale-stealable when its pid is dead) guarantees that of N
        supervisors recovering the same directory exactly one resubmits
        each pending cell.  The loser returns immediately with
        ``claimed: False`` and who beat it.  Returns ``{"requests":
        [Request...], "expired": [items], "claimed": bool}``."""
        me = claimant or f"fleet-{id(self):x}"
        if not FleetJournal.claim_recovery(journal_dir, me):
            self.metrics.inc("journal-claim-lost")
            return {"requests": [], "expired": [], "claimed": False,
                    "claimed-by": FleetJournal.claim_holder(journal_dir)}
        rec = FleetJournal.recover(journal_dir)
        reqs = []
        for item in rec["pending"]:
            reqs.append(self.submit(item["history"], **item["kwargs"]))
        if rec["pending"]:
            self.metrics.inc("journal-recovered", len(rec["pending"]))
        if rec["expired"]:
            self.metrics.inc("journal-expired", len(rec["expired"]))
        return {"requests": reqs, "expired": rec["expired"],
                "claimed": True}

    # -- core.analyze routing (shared with CheckService) ------------------
    _routable = CheckService._routable
    try_route_analyze = CheckService.try_route_analyze

    # -- lifecycle --------------------------------------------------------
    def queue_depth(self) -> int:
        with self._lock:
            return len(self._open_cells)

    def workers_snapshot(self) -> List["FleetWorker"]:
        """Point-in-time copy of the slot list.  ``add_worker`` appends
        under the fleet lock, so the heartbeat/supervisor/export threads
        must not iterate the live list — they iterate this copy; the
        slot objects themselves carry their own breaker/health locks."""
        with self._lock:
            return list(self.workers)

    def _is_closed(self) -> bool:
        with self._lock:
            return self._closed

    def alive(self) -> bool:
        return not self._is_closed() and any(
            w.alive() for w in self.workers_snapshot())

    def drain(self, timeout: Optional[float] = None) -> bool:
        deadline = (mono_now() + timeout) if timeout is not None else None
        with self._cond:
            while self._open_cells:
                left = None if deadline is None else deadline - mono_now()
                if left is not None and left <= 0:
                    return False
                self._cond.wait(timeout=left if left is not None else 0.1)
            return True

    def close(self, timeout: Optional[float] = None) -> bool:
        """Stop admitting, drain every open cell (each admitted request
        still resolves), then shut the workers down."""
        with self._lock:
            if self._closed:
                return True
        ok = self.drain(timeout=timeout)
        with self._lock:
            self._closed = True
        self._pool.shutdown(wait=True)
        for w in self.workers_snapshot():
            try:
                w.service.close(timeout=timeout)
            except Exception:  # noqa: BLE001 — close the rest regardless
                log.exception("worker %d close failed", w.wid)
        if self._hb_thread.is_alive():
            self._hb_thread.join(timeout=2 * self.heartbeat_s + 1.0)
        return ok

    def kill(self) -> None:
        """Abrupt whole-fleet death (crash semantics, for recovery
        tests): no drain, workers killed, open cells left in the journal
        for :meth:`recover`."""
        with self._lock:
            self._closed = True
        for w in self.workers_snapshot():
            try:
                w.kill()
            except Exception:  # noqa: BLE001
                pass
        self._pool.shutdown(wait=False, cancel_futures=True)

    def __enter__(self) -> "Fleet":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# ---------------------------------------------------------------------------
# out-of-process workers on a real wire
# ---------------------------------------------------------------------------


class ProcWorker(FleetWorker):
    """A worker slot whose service lives across a socket: the
    :class:`~jepsen_tpu.serve.transport.ProcWorkerService` facade over a
    launcher (real subprocess or in-process thread server), dialed
    through this slot's stable :class:`~jepsen_tpu.net_proxy.PairProxy`
    link so the chaos harness owns the wire."""

    def __init__(self, wid: int, make_service, proxy: PairProxy,
                 devices: Optional[list] = None,
                 fail_threshold: int = 3, open_s: float = 1.0):
        self.proxy = proxy
        super().__init__(wid, make_service, devices=devices,
                         fail_threshold=fail_threshold, open_s=open_s)

    def status(self) -> Dict[str, Any]:
        st = super().status()
        st["link"] = {"proxy-port": self.proxy.port,
                      "severed": self.proxy.severed,
                      "delay-s": self.proxy.delay_s}
        remote = getattr(self.service, "remote_status", None)
        if remote is not None:
            try:
                st["proc"] = remote()
            except Exception:  # noqa: BLE001 — status never raises
                pass
        return st


class ProcFleet(Fleet):
    """The fleet with every worker out of process and every byte of the
    submit surface on a real wire.

    Each slot runs ``python -m jepsen_tpu.serve.worker_main`` as its own
    OS process (``spawn=True``; ``spawn=False`` hosts the identical
    protocol server on a thread for tier-1 CI), dialed through a
    per-slot PairProxy whose port is stable across worker respawns
    (``retarget``).  That link is what upgrades the chaos harness from
    scheduler-patching faults to true network faults: partition
    (RST + ECONNREFUSED), mid-frame cuts, slow links, reconnect storms.

    A supervisor thread respawns crashed worker *processes* into their
    slots — the process-tier analogue of ``restart_worker`` — while the
    per-cell drivers handle the requests the corpse stranded (transport
    unknowns → reroute), and the journal claim keeps a crashed
    *supervisor*'s recovery single-winner."""

    def __init__(self, workers: int = 3, *,
                 spawn: bool = True,
                 log_dir: Optional[str] = None,
                 supervise_s: float = 0.5,
                 worker_ready_timeout_s: float = 120.0,
                 **kw):
        self._spawn = spawn
        self._log_dir = log_dir
        self.supervise_s = supervise_s
        self.worker_ready_timeout_s = worker_ready_timeout_s
        self.proxies: List[PairProxy] = []
        self._sup_lock = threading.Lock()
        # subprocess workers already pin nothing useful from the parent;
        # device pinning is the worker process's own business
        kw.setdefault("pin_devices", False)
        # resolved before super().__init__ because _make_workers (called
        # from there) builds WireClients that share the fleet's policy
        kw.setdefault("retry_policy", RetryPolicy(
            tries=4, backoff_s=0.02, max_backoff_s=0.5, decorrelated=True))
        self.retry_policy = kw["retry_policy"]
        super().__init__(workers, **kw)
        self._sup_thread = threading.Thread(
            target=self._supervise_loop, daemon=True,
            name="procfleet-supervisor")
        self._sup_thread.start()

    def _make_workers(self, n: int, lanes_each: int,
                      device_sets: List[list], *, mesh,
                      capacity: Optional[int], max_capacity: int,
                      fail_threshold: int,
                      open_s: float) -> List[FleetWorker]:
        lanes = proc_worker_lanes(self.max_lanes, n)
        if self._log_dir is None:
            import tempfile
            self._log_dir = tempfile.mkdtemp(prefix="procfleet-logs-")
        workers: List[FleetWorker] = []
        for i in range(n):
            # the target is retargeted at the worker's real port once
            # its launcher reports ready; port 1 can never accept, so a
            # dial before readiness fails fast instead of hanging
            proxy = PairProxy("fleet", f"worker-{i}", ("127.0.0.1", 1))
            self.proxies.append(proxy)
            workers.append(ProcWorker(
                i, self._make_proc_service(i, lanes, proxy,
                                           capacity=capacity,
                                           max_capacity=max_capacity),
                proxy, devices=[],
                fail_threshold=fail_threshold, open_s=open_s))
        return workers

    def _make_proc_service(self, i: int, lanes: int, proxy: PairProxy, *,
                           capacity: Optional[int], max_capacity: int):
        from jepsen_tpu.serve.transport import ProcWorkerService
        from jepsen_tpu.serve.worker_main import (SubprocessWorker,
                                                  ThreadWorker)
        name = f"proc-worker-{i}"
        spawn = self._spawn
        log_dir = self._log_dir
        ready_s = self.worker_ready_timeout_s
        mqc = self.max_queue_cells

        tele_s = self.telemetry_s

        def make():
            if spawn:
                launcher = SubprocessWorker(
                    name, os.path.join(log_dir, f"{name}.log"),
                    args={"max-lanes": lanes, "max-queue": mqc,
                          "capacity": capacity,
                          "max-capacity": max_capacity,
                          "telemetry-s": tele_s},
                    ready_timeout_s=ready_s)
            else:
                launcher = ThreadWorker(
                    name,
                    lambda: CheckService(max_queue_cells=mqc,
                                         max_lanes=lanes,
                                         capacity=capacity,
                                         max_capacity=max_capacity),
                    telemetry_s=tele_s)
            svc = ProcWorkerService(launcher, proxy,
                                    retry_policy=self.retry_policy,
                                    name=name)
            # TELEMETRY pushes from this slot land wid-tagged in the
            # fleet's store (the sink survives respawns: every fresh
            # service from this factory re-registers it)
            svc.on_telemetry = \
                lambda payload: self._note_worker_telemetry(i, payload)
            return svc
        return make

    # -- the supervisor ----------------------------------------------------
    def _supervise_loop(self) -> None:
        while not self._is_closed():
            for w in self.workers_snapshot():
                try:
                    if self._maybe_respawn(w):
                        self.metrics.inc("supervisor-respawns")
                except Exception:  # noqa: BLE001 — a failed respawn
                    log.exception("supervisor respawn of worker %d "
                                  "failed", w.wid)  # retries next sweep
            time.sleep(self.supervise_s)

    def _maybe_respawn(self, w: FleetWorker) -> bool:
        """Respawn ``w`` iff its process is dead and the fleet is open.
        The sup lock + ``only_if_dead`` make the supervisor, a chaos
        undo, and a manual ``restart_worker`` mutually exclusive: one
        respawner wins, the rest observe the fresh service.  Retired
        slots (scale-down, decommission_worker) stay dead: respawning
        one would undo the Governor's drain."""
        if w.alive() or w.retired:
            return False
        with self._sup_lock:
            # fleet lock under the sup lock is manifest-descending
            if self._is_closed() or w.alive() or w.retired:
                return False
            if w.restart(only_if_dead=True):
                self.metrics.inc("worker-restarts")
                return True
            return False

    # -- lifecycle ---------------------------------------------------------
    def close(self, timeout: Optional[float] = None) -> bool:
        ok = super().close(timeout=timeout)
        self._join_supervisor()
        # an in-flight respawn may have installed a fresh service after
        # super().close() swept the old ones: final sweep under the sup
        # lock catches it (ProcWorkerService.close is idempotent)
        with self._sup_lock:
            for w in self.workers_snapshot():
                try:
                    w.service.close(timeout=5.0)
                except Exception:  # noqa: BLE001
                    pass
        for p in self.proxies:
            p.close()
        return ok

    def kill(self) -> None:
        super().kill()
        self._join_supervisor()
        with self._sup_lock:
            for w in self.workers_snapshot():
                try:
                    w.service.kill()
                except Exception:  # noqa: BLE001
                    pass
        for p in self.proxies:
            p.close()

    def _join_supervisor(self) -> None:
        t = getattr(self, "_sup_thread", None)
        if t is not None and t.is_alive():
            t.join(timeout=2 * self.supervise_s + 1.0)
