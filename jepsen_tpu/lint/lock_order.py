"""The declared lock-acquisition order for the threaded subsystems.

serve/ and monitor/ are the two places where several threads (submitters,
the scheduler device loop, the monitor flusher, web handlers) share
state.  Deadlock freedom there rests on a total order: a thread holding
lock L may only acquire locks strictly *later* in this manifest.  The
CONC01 rule enforces the order syntactically — any ``with`` acquiring a
declared lock lexically inside a ``with`` holding a later-or-equal one
is a finding — so a PR that introduces an inversion fails CI instead of
deadlocking a service under load.

Each entry is ``(name, [(path_regex, expr_regex), ...])``: a ``with``
item matches the entry when its file path matches ``path_regex`` and the
unparsed context expression matches ``expr_regex``.  Level = position in
the tuple (earlier = outermost-permitted).

The declared order mirrors the call graph today:

    fleet-supervisor -> autoscale -> fleet -> fleet-registry
      -> fleet-slot
      -> fleet-journal-write -> fleet-journal-pending
      -> transport-ready -> transport-state -> transport-send
      -> procworker-state -> procworker-send
      -> service -> scheduler -> request -> metrics -> tenants
    router (leaf: breaker/health state, never wraps another lock)
    monitor-flush -> monitor-registry -> verdict -> tap
    engine-cache (leaf: engine.cache's shared LRU, acquired under anything)
    obs-hist, obs-recorder, obs-telemetry, obs-slo (leaves: the
      histogram set's, flight recorder's, telemetry store's, and SLO
      engine's own locks — observe/record/push is called from under
      scheduler/fleet/metrics code and from wire reader threads, so
      these must never wrap another declared lock)

The journal pair is the FleetJournal's write/pending discipline:
``_flush`` snapshots the pending map *inside* the writer lock
(``fleet-journal-write`` then ``fleet-journal-pending``) so a slow
earlier writer can't clobber a newer snapshot; record/complete take the
pending lock alone and flush after releasing it.

The transport chain follows a respawn end to end: the ProcFleet
supervisor (``_sup_lock`` — the Fleetport's slot-admission/eviction
lock sits at the same level, and holds the registry's membership lock
(``fleet-registry``) beneath it when binding slots), restarts a slot
(``_restart_lock``), whose
new ProcWorkerService builds its wire under ``_ready_lock``; the
WireClient guards connection + pending-table state with its ``_lock``
and serializes frame writes with ``_send_lock``; worker-side, the
WorkerServer's table lock precedes each connection's send lock, and a
ThreadWorker's in-process CheckService sits underneath all of it.
"""

from __future__ import annotations

import re
from typing import List, Optional, Tuple

LOCK_ORDER: Tuple[Tuple[str, List[Tuple[str, str]]], ...] = (
    ("fleet-supervisor",
     [(r"serve/fleet\.py$", r"^self\._sup_lock$"),
      (r"serve/fleetport\.py$", r"^self\._sup_lock$")]),
    # the Governor's policy-state lock (serve/autoscale.py): decisions
    # are made under it, but signal reads and scale actions — which take
    # fleet/scheduler locks — happen outside; it sits above "fleet" so
    # holding it across a fleet call could never invert
    ("autoscale",
     [(r"serve/autoscale\.py$", r"^self\._lock$")]),
    ("fleet",
     [(r"serve/fleet\.py$", r"^self\._(lock|cond)$")]),
    ("fleet-registry",
     [(r"serve/registry\.py$", r"^self\._lock$")]),
    ("fleet-slot",
     [(r"serve/fleet\.py$", r"^self\._restart_lock$"),
      (r"", r"^(w|worker)\._restart_lock$")]),
    ("fleet-journal-write",
     [(r"serve/fleet\.py$", r"^self\._wlock$")]),
    ("fleet-journal-pending",
     [(r"serve/fleet\.py$", r"^self\._jlock$")]),
    ("transport-ready",
     [(r"serve/transport\.py$", r"^self\._ready_lock$")]),
    ("transport-state",
     [(r"serve/transport\.py$", r"^self\._lock$")]),
    ("transport-send",
     [(r"serve/transport\.py$", r"^self\._send_lock$")]),
    ("procworker-state",
     [(r"serve/worker_main\.py$", r"^self\._lock$")]),
    ("procworker-send",
     [(r"serve/worker_main\.py$", r"^(self|c|cs|conn)\._send_lock$")]),
    ("service",
     [(r"serve/service\.py$", r"^self\._lock$")]),
    ("scheduler",
     [(r"serve/scheduler\.py$", r"^self\._(lock|cond)$")]),
    ("request",
     [(r"serve/request\.py$", r"^self\._lock$"),
      (r"", r"^(req|request)\._lock$"),
      (r"", r"^(c|cell)\.request\._lock$")]),
    ("metrics",
     [(r"serve/metrics\.py$", r"^self\._lock$")]),
    # the tenant table's quota condition (serve/tenants.py): submit
    # paths block on it BEFORE touching the scheduler, and exports read
    # counts outside the metrics lock — near-leaf, wraps nothing
    ("tenants",
     [(r"serve/tenants\.py$", r"^self\._cond$")]),
    ("router",
     [(r"serve/router\.py$", r"^self\._lock$")]),
    ("monitor-flush",
     [(r"monitor/__init__\.py$", r"^self\._flush_lock$")]),
    ("monitor-registry",
     [(r"monitor/__init__\.py$", r"^_REG_LOCK$")]),
    ("verdict",
     [(r"monitor/verdict\.py$", r"^self\._lock$")]),
    ("tap",
     [(r"monitor/tap\.py$", r"^self\._lock$")]),
    ("engine-cache",
     [(r"engine/cache\.py$", r"^self\._lock$")]),
    # the fission planes' stats-counter locks (fleet edge and the
    # engine's shrink recursion): _bump/snapshot only — touched from
    # under fleet/scheduler/metrics code, so leaves by construction
    ("fission-plane",
     [(r"serve/fission_plane\.py$", r"^_STATS_LOCK$")]),
    ("shrink",
     [(r"engine/shrink\.py$", r"^_STATS_LOCK$")]),
    ("obs-hist",
     [(r"obs/hist\.py$", r"^self\._lock$"),
      (r"obs/hist\.py$", r"^_MERGE_LOCK$"),
      (r"obs/hist\.py$", r"^_FIRST_USE_LOCK$")]),
    ("obs-recorder",
     [(r"obs/recorder\.py$", r"^self\._lock$")]),
    ("obs-telemetry",
     [(r"obs/telemetry\.py$", r"^self\._lock$"),
      (r"obs/telemetry\.py$", r"^_GAUGE_LOCK$")]),
    ("obs-slo",
     [(r"obs/slo\.py$", r"^self\._lock$")]),
)


def lock_level(path: str, expr: str) -> Optional[Tuple[int, str]]:
    """(level, name) of the declared lock a with-item acquires, or None
    when the expression is not a declared lock."""
    for level, (name, patterns) in enumerate(LOCK_ORDER):
        for path_re, expr_re in patterns:
            if re.search(path_re, path) and re.match(expr_re, expr):
                return level, name
    return None
