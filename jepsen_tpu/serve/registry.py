"""The fleet membership registry: who is serving, from where, until when.

serve/fleet.py's constructor builds a *fixed* worker set — N slots, all
local, known before the first request.  A multi-host fleet cannot know
its members up front: workers on other machines REGISTER over the wire
(serve/fleetport.py), advertise where to dial them back
(``host:port``), what they are (device inventory, mesh shape, capability
buckets), and then hold a **lease**.  Every telemetry/heartbeat push
renews it; a worker that stops pushing — crashed, partitioned, or
decommissioned, indistinguishable from here and deliberately treated
the same — simply stops renewing, and the lease reaper evicts it
without any local signal.  Eviction is the multi-host analogue of
SIGKILL-the-slot: the slot goes dead, the router's rendezvous ranking
reroutes the worker's keys to siblings, and the journal's entries drain
through the normal driver reroute path.

Mesh shapes are the placement vocabulary: a worker advertising a 4×2
device mesh offers ``4*2*64 = 512`` lanes per dispatch, so a 512-lane
elle group can only land there; a CPU CI worker advertises the
degenerate ``(1,)`` mesh (64 lanes) and takes everything today's tests
route (see ``WorkerRecord.max_lanes`` / ``Router.ranked``).

All lease arithmetic runs on the monotonic clock
(:func:`jepsen_tpu.clock.mono_now`) — a wall-clock lease steps under
NTP adjustment and evicts healthy workers (or keeps dead ones) on a
time jump; CONC01 enforces this, and the registry lock's place in the
declared order is ``fleet-registry`` (lint/lock_order.py): below the
fleet locks, above the per-slot restart lock.

The registry never stores or exports the fleet auth token; its
snapshots are safe to serve from ``GET /fleet`` verbatim.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from jepsen_tpu.clock import mono_now

#: lanes one device contributes to a dispatch (the serve tier's
#: max-lanes default per worker; 8 devices x 64 = the 512-lane ceiling
#: in engine/ladder.MAX_LANE_BUCKET)
LANES_PER_DEVICE = 64

#: default lease duration, seconds (env-overridable)
DEFAULT_LEASE_S = 10.0

#: how many evicted-worker snapshots the registry remembers
EVICTED_RING = 64


def lease_duration_s() -> float:
    """The configured lease duration: ``JEPSEN_TPU_LEASE_S`` (seconds,
    must be > 0) or the 10 s default.  Read at call time so tests and
    the CLI can retune without re-importing."""
    raw = os.environ.get("JEPSEN_TPU_LEASE_S", "")
    try:
        v = float(raw) if raw else DEFAULT_LEASE_S
    except ValueError:
        return DEFAULT_LEASE_S
    return v if v > 0 else DEFAULT_LEASE_S


def parse_mesh(spec: Any) -> Tuple[int, ...]:
    """A mesh shape from wire/CLI forms: ``"4x2"`` / ``[4, 2]`` /
    ``(4, 2)`` → ``(4, 2)``; anything empty or malformed degrades to
    the degenerate ``(1,)`` mesh — a worker that cannot say what it is
    gets the smallest placement claim, never a bigger one."""
    if isinstance(spec, str):
        parts = [p for p in spec.replace("X", "x").split("x") if p]
        try:
            dims = tuple(int(p) for p in parts)
        except ValueError:
            return (1,)
    elif isinstance(spec, (list, tuple)):
        try:
            dims = tuple(int(d) for d in spec)
        except (TypeError, ValueError):
            return (1,)
    else:
        return (1,)
    if not dims or any(d < 1 for d in dims):
        return (1,)
    return dims


def mesh_lanes(mesh: Sequence[int]) -> int:
    """Lane capacity a mesh shape offers per dispatch."""
    n = 1
    for d in mesh:
        n *= max(1, int(d))
    return n * LANES_PER_DEVICE


@dataclass
class WorkerRecord:
    """One registered worker: identity, dial-back address, inventory,
    and the lease.  ``wid`` is assigned by the fleet when the record
    gets a slot; ``generation`` counts re-registrations under the same
    name (a worker that was evicted and came back)."""

    name: str
    host: str
    port: int
    pid: Optional[int] = None
    devices: Tuple[str, ...] = ()
    mesh: Tuple[int, ...] = (1,)
    buckets: Tuple[str, ...] = ()
    wid: Optional[int] = None
    generation: int = 0
    registered_at: float = field(default_factory=mono_now)
    lease_expires_at: float = 0.0
    renewals: int = 0
    evicted: bool = False

    @property
    def max_lanes(self) -> int:
        return mesh_lanes(self.mesh)

    def fits_lanes(self, lanes: int) -> bool:
        return int(lanes) <= self.max_lanes

    def lease_remaining_s(self, now: Optional[float] = None) -> float:
        now = mono_now() if now is None else now
        return self.lease_expires_at - now

    def snapshot(self, now: Optional[float] = None) -> Dict[str, Any]:
        now = mono_now() if now is None else now
        return {"name": self.name, "wid": self.wid,
                "host": self.host, "port": self.port, "pid": self.pid,
                "devices": list(self.devices),
                "mesh": "x".join(str(d) for d in self.mesh),
                "max-lanes": self.max_lanes,
                "buckets": list(self.buckets),
                "generation": self.generation,
                "renewals": self.renewals,
                "age-s": round(max(now - self.registered_at, 0.0), 3),
                "lease-remaining-s": round(self.lease_remaining_s(now), 3),
                "evicted": self.evicted}


class FleetRegistry:
    """Thread-safe membership + lease table.  Writers are the fleetport
    accept threads (register/renew) and the lease reaper (expire);
    readers are the router, ``GET /fleet``, and the metrics scrape."""

    def __init__(self, lease_s: Optional[float] = None):
        self.lease_s = float(lease_s) if lease_s else lease_duration_s()
        self._lock = threading.Lock()
        self._records: Dict[str, WorkerRecord] = {}   # live, by name
        self._gens: Dict[str, int] = {}
        self._blocked: set = set()   # names whose renewals chaos holds
        self._evicted: List[Dict[str, Any]] = []
        self.evictions = 0
        self.registrations = 0

    # -- membership --------------------------------------------------------
    def register(self, name: str, host: str, port: int, *,
                 pid: Optional[int] = None,
                 devices: Sequence[str] = (),
                 mesh: Any = (1,),
                 buckets: Sequence[str] = (),
                 now: Optional[float] = None
                 ) -> Tuple[Optional[WorkerRecord], bool]:
        """Admit (or refresh) one worker.  Returns ``(record, created)``
        — ``created`` is False when a live record under this name was
        renewed/updated in place, True when this registration made a new
        record (first contact, or a comeback after eviction: the
        generation bumps so stale pushes from the old incarnation are
        distinguishable).  Returns ``(None, False)`` when the name is
        chaos-blocked and holds no live record: the fault models a
        worker partitioned from the control plane, and a partitioned
        worker cannot re-register its way back in either — only the
        heal (``unblock_renewals``) reopens the door."""
        now = mono_now() if now is None else now
        with self._lock:
            rec = self._records.get(name)
            if rec is not None and not rec.evicted:
                rec.host, rec.port, rec.pid = str(host), int(port), pid
                rec.devices = tuple(str(d) for d in devices)
                rec.mesh = parse_mesh(mesh)
                rec.buckets = tuple(str(b) for b in buckets)
                if name not in self._blocked:
                    # a blocked live record keeps its (force-expired)
                    # lease: a refresh must not outrun the reaper
                    rec.lease_expires_at = now + self.lease_s
                    rec.renewals += 1
                return rec, False
            if name in self._blocked:
                return None, False
            gen = self._gens.get(name, -1) + 1
            self._gens[name] = gen
            rec = WorkerRecord(
                name=name, host=str(host), port=int(port), pid=pid,
                devices=tuple(str(d) for d in devices),
                mesh=parse_mesh(mesh),
                buckets=tuple(str(b) for b in buckets),
                generation=gen, registered_at=now,
                lease_expires_at=now + self.lease_s)
            self._records[name] = rec
            self.registrations += 1
            return rec, True

    def bind_slot(self, name: str, wid: int) -> None:
        """Record which fleet slot serves this name (fleet-side only)."""
        with self._lock:
            rec = self._records.get(name)
            if rec is not None:
                rec.wid = int(wid)

    # -- leases ------------------------------------------------------------
    def renew(self, name: str, now: Optional[float] = None) -> bool:
        """Extend a live worker's lease (telemetry/heartbeat path).
        False when the name is unknown, already evicted, or its
        renewals are chaos-blocked — a blocked renewal must not
        resurrect a lease the fault is expiring."""
        now = mono_now() if now is None else now
        with self._lock:
            rec = self._records.get(name)
            if rec is None or rec.evicted or name in self._blocked:
                return False
            rec.lease_expires_at = now + self.lease_s
            rec.renewals += 1
            return True

    def force_expire(self, name: str,
                     now: Optional[float] = None) -> bool:
        """Backdate a lease to expired-now (the chaos fault's trigger)."""
        now = mono_now() if now is None else now
        with self._lock:
            rec = self._records.get(name)
            if rec is None or rec.evicted:
                return False
            rec.lease_expires_at = now
            return True

    def block_renewals(self, name: str) -> None:
        with self._lock:
            self._blocked.add(name)

    def unblock_renewals(self, name: str) -> None:
        with self._lock:
            self._blocked.discard(name)

    def expire_leases(self, now: Optional[float] = None
                      ) -> List[WorkerRecord]:
        """Pop every record whose lease is spent (the reaper's sweep).
        The popped records are marked evicted and remembered in a
        bounded ring for ``GET /fleet``'s recent-evictions view."""
        now = mono_now() if now is None else now
        out: List[WorkerRecord] = []
        with self._lock:
            for name in [n for n, r in self._records.items()
                         if r.lease_expires_at <= now]:
                rec = self._records.pop(name)
                rec.evicted = True
                self.evictions += 1
                self._evicted.append(rec.snapshot(now))
                del self._evicted[:-EVICTED_RING]
                out.append(rec)
        return out

    # -- reads -------------------------------------------------------------
    def get(self, name: str) -> Optional[WorkerRecord]:
        with self._lock:
            return self._records.get(name)

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._records)

    def is_live(self, name: str,
                generation: Optional[int] = None) -> bool:
        """Is this name currently a member (lease not yet reaped)?  With
        ``generation``, additionally require the live record to BE that
        incarnation — an evicted worker's old launcher must read dead
        even after the name re-registers."""
        with self._lock:
            rec = self._records.get(name)
            if rec is None or rec.evicted:
                return False
            if generation is not None and rec.generation != generation:
                return False
            return True

    def lease_age_s(self, name: str,
                    now: Optional[float] = None) -> Optional[float]:
        """Seconds since this worker last renewed (0 right after a
        renewal, climbing toward ``lease_s`` as it goes quiet)."""
        now = mono_now() if now is None else now
        with self._lock:
            rec = self._records.get(name)
            if rec is None:
                return None
            return max(now - (rec.lease_expires_at - self.lease_s), 0.0)

    def max_lease_age_s(self, now: Optional[float] = None) -> float:
        """The staleness high-water mark across the membership — the
        gauge the telemetry plane exports (obs/telemetry.py)."""
        now = mono_now() if now is None else now
        ages = [self.lease_age_s(n, now=now) for n in self.names()]
        return max([a for a in ages if a is not None], default=0.0)

    def snapshot(self, now: Optional[float] = None) -> Dict[str, Any]:
        """The ``GET /fleet`` membership document.  Carries no secret:
        auth status is a boolean, never the token."""
        now = mono_now() if now is None else now
        with self._lock:
            live = [r.snapshot(now) for r in self._records.values()]
            evicted = [dict(e) for e in self._evicted]
            blocked = sorted(self._blocked)
            registrations = self.registrations
            evictions = self.evictions
        live.sort(key=lambda r: (r["wid"] is None, r["wid"], r["name"]))
        return {"lease-s": self.lease_s,
                "workers": live,
                "registrations": registrations,
                "evictions": evictions,
                "renewals-blocked": blocked,
                "recent-evictions": evicted}
