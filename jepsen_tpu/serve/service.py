"""CheckService: the persistent in-process batched checking service.

One service owns one scheduler (one device loop) and accepts history-
check requests from any number of threads — concurrent test runs,
cli.py's ``submit`` command via the web endpoint, the web UI.  Requests
are decomposed into per-key cells, shape-bucketed, and continuously
batched onto the vmapped wgl / elle_tpu engines; verdicts come back
through the aggregator under the established never-degrade-to-false
rules.  See docs/serving.md.

Usage::

    with CheckService() as svc:
        req = svc.submit(history, kind="wgl", model="cas-register")
        result = req.wait()
        # or one-shot:
        result = svc.check(history, kind="elle", workload="list-append")

``core.analyze`` routes through a service automatically when the test
map carries one under ``test["service"]`` (see try_route_analyze), which
is how ``cli.test_all_cmd`` shares one device across a campaign.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional, Union

from jepsen_tpu.history import History
from jepsen_tpu.serve.aggregate import aggregate, expired_result
from jepsen_tpu.serve.decompose import decompose
from jepsen_tpu.serve.metrics import Metrics, mono_now
from jepsen_tpu.serve.request import KIND_ELLE, KIND_WGL, Request
from jepsen_tpu.serve.scheduler import Scheduler
from jepsen_tpu.serve.tenants import TenantTable


class ServiceSaturated(RuntimeError):
    """Admission control rejected the request (queue at max depth)."""


class ServiceClosed(RuntimeError):
    """The service is shut down; no new requests are admitted."""


def build_spec(kind: str, *, model=None, workload: str = "list-append",
               realtime: bool = False, consistency_models=None,
               engine: str = "auto", **engine_opts) -> Dict[str, Any]:
    """Normalize submit kwargs into a request spec — shared by
    CheckService.submit and the fleet's router (serve.fleet), so the two
    admission paths cannot drift on what a spec means."""
    if kind == KIND_WGL:
        if isinstance(model, str) or model is None:
            from jepsen_tpu.models import get_model
            model = get_model(model or "cas-register")
        return {"model": model, **engine_opts}
    if kind == KIND_ELLE:
        return {"workload": workload, "realtime": realtime,
                "consistency_models": consistency_models,
                "engine": engine, **engine_opts}
    raise ValueError(f"unknown kind {kind!r}")


def submit_kwargs(req: Request) -> Dict[str, Any]:
    """Invert :func:`build_spec`: the kwargs that re-submit ``req``'s
    spec to another service — the fleet's reroute/hedge path and journal
    recovery both re-enqueue cells this way.  (build_spec is idempotent
    on its own output, so round-tripping is safe.)"""
    return {"kind": req.kind, **req.spec}


class _ServiceRouted:
    """Checker adapter: ``check`` submits to the service (used for the
    serviceable children of a composed checker, so Compose's merge and
    crash handling stay authoritative).  Falls back to the wrapped
    checker's direct path if routing declines."""

    def __init__(self, service: "CheckService", inner):
        self.service = service
        self.inner = inner

    def check(self, test, history, opts=None):
        routed = self.service.try_route_analyze(test, self.inner, history,
                                                opts)
        if routed is not None:
            return routed
        # Compose already wraps this call in check_safe — crashes and
        # budgets are handled one level up; don't double-wrap.
        return self.inner.check(test, history, opts)


class CheckService:
    def __init__(self,
                 max_queue_cells: int = 4096,
                 max_lanes: int = 64,
                 default_deadline_s: Optional[float] = None,
                 mesh=None,
                 capacity: Optional[int] = None,
                 max_capacity: int = 65536,
                 age_s: Optional[float] = None,
                 device=None):
        # Shared init: repeated service processes skip XLA compiles.
        import jax
        from jepsen_tpu.ops.cache import init_compilation_cache
        from jepsen_tpu.serve.scheduler import DEFAULT_AGE_S
        init_compilation_cache()
        #: the platform this service's device engines run on, as JAX
        #: reports it — ping()/status carry it so a worker that got no
        #: chip says "cpu" instead of looking like a chip worker
        self.platform = (device.platform if device is not None
                         else jax.default_backend())
        self.max_queue_cells = max_queue_cells
        self.default_deadline_s = default_deadline_s
        self.metrics = Metrics()
        # capacity None = per-bucket derived wgl start capacity (see
        # ladder.wgl_start_capacity; JEPSEN_TPU_WGL_CAPACITY overrides)
        self._sched = Scheduler(self.metrics, mesh=mesh,
                                max_lanes=max_lanes, capacity=capacity,
                                max_capacity=max_capacity,
                                age_s=age_s if age_s is not None
                                else DEFAULT_AGE_S,
                                device=device)
        self._closed = False
        self._lock = threading.Lock()
        self._submitted = 0
        # multi-tenant QoS: quotas/priorities from JEPSEN_TPU_TENANT_*
        # (serve/tenants.py); tenantless submits bypass the table
        self.tenants = TenantTable.from_env()
        self.metrics.bind(self._sched.depth, self._inflight)
        self.metrics.bind_queue(self._sched.occupancy)
        self.metrics.bind_tenants(self.tenants.counts)
        self._sched.start()

    def _inflight(self) -> int:
        # bound gauge; counter() takes the metrics lock briefly, which
        # is safe here because snapshot() samples gauges outside it
        completed = self.metrics.counter("requests-completed")
        return max(0, self._submitted - completed)

    # -- submission -------------------------------------------------------
    def submit(self, history: History, *,
               kind: str = KIND_WGL,
               model: Union[str, Any, None] = None,
               workload: str = "list-append",
               realtime: bool = False,
               consistency_models=None,
               engine: str = "auto",
               deadline_s: Optional[float] = None,
               block: bool = True,
               timeout: Optional[float] = None,
               trace: Optional[Dict[str, Any]] = None,
               tenant: Optional[str] = None,
               **engine_opts) -> Request:
        """Enqueue one history check; returns a :class:`Request` handle
        (``.wait()`` for the verdict).  ``block=False`` raises
        :class:`ServiceSaturated` instead of waiting out backpressure.

        ``trace`` is a propagated trace context (obs.trace wire dict)
        from an upstream hop — the fleet's root request, a remote
        client.  It rides beside the spec (never inside it, so reroute/
        journal round-trips through build_spec don't see it) and makes
        this request a child span of the sender's.  ``tenant`` rides the
        same way: it names the submitting tenant for quota accounting,
        priority class, and the per-tenant metrics cut (serve/tenants.py).

        A request whose deadline expires *while blocked on admission* —
        whether on its tenant's quota or on global backpressure —
        resolves ``unknown`` (the returned handle is already done) rather
        than raising: backpressure is indistinguishable from a slow
        device to the caller, and the deadline contract is "unknown,
        never dropped, never false" on every path — including the
        admission path."""
        if self._closed:
            raise ServiceClosed("service is closed")
        spec = build_spec(kind, model=model, workload=workload,
                          realtime=realtime,
                          consistency_models=consistency_models,
                          engine=engine, **engine_opts)
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        req = Request(history, kind, spec, deadline_s=deadline_s,
                      trace=trace, tenant=tenant,
                      priority=self.tenants.priority(tenant))
        cells = decompose(req)
        # A blocked offer never outlives the deadline: the expiring
        # request must surface unknown, not sit in admission forever.
        rem = req.remaining_s()
        if rem is not None:
            timeout = rem if timeout is None else min(timeout, rem)
        # Tenant quota gate (before global backpressure): a blocked
        # acquire is bounded by the same deadline/timeout as the offer,
        # and the same expiry contract applies — over quota at deadline
        # is unknown, never false, never dropped.
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
        # the slot frees on *every* finish path (request.finish fires it)
        req.on_finish = lambda t=tenant: self.tenants.release(t)
        if not self._sched.offer(cells, block=block,
                                 max_depth=self.max_queue_cells,
                                 timeout=timeout):
            if req.expired():
                return self._finish_expired(req, cells)
            self.tenants.release(tenant)
            req.on_finish = None
            self.metrics.inc("requests-rejected")
            raise ServiceSaturated(
                f"queue at {self._sched.depth()}/{self.max_queue_cells} "
                f"cells; request of {len(cells)} cell(s) rejected")
        with self._lock:
            self._submitted += 1
        self.metrics.inc("requests-submitted")
        self.metrics.inc("cells-submitted", len(cells))
        return req

    def _finish_expired(self, req: Request, cells) -> Request:
        """The expiry-while-blocked path: resolve every cell unknown and
        hand back a completed request — shared by the tenant-quota and
        global-backpressure admission gates."""
        for c in cells:
            c.result = expired_result(req.kind)
        self.metrics.inc("deadline-expired", len(cells))
        with self._lock:
            self._submitted += 1
        self.metrics.inc("requests-submitted")
        self.metrics.inc("cells-submitted", len(cells))
        self.metrics.inc("cells-completed", len(cells))
        self.metrics.inc("requests-completed")
        req.finish(aggregate(req))
        self.metrics.trace(req)
        return req

    def check(self, history: History, *, timeout: Optional[float] = None,
              **kw) -> Dict[str, Any]:
        """Submit and wait: the synchronous convenience path."""
        return self.submit(history, **kw).wait(timeout=timeout)

    # -- core.analyze routing ---------------------------------------------
    def _routable(self, checker) -> bool:
        """Cheap predicate: would :meth:`try_route_analyze` service this
        checker?  (No submission, no side effects.)"""
        from jepsen_tpu.checker.linearizable import Linearizable
        from jepsen_tpu.independent import IndependentChecker
        inner = checker.inner if isinstance(checker, IndependentChecker) \
            else checker
        if isinstance(inner, Linearizable):
            return (inner._jax_model() is not None
                    and inner.algorithm in (None, "tpu"))
        try:
            from jepsen_tpu.checker.elle import ElleChecker
        except Exception:  # noqa: BLE001
            return False
        return (isinstance(checker, ElleChecker)
                and checker.engine in ("auto", "tpu"))

    def try_route_analyze(self, test, checker, history: History,
                          opts=None) -> Optional[Dict[str, Any]]:
        """Route a test's analysis through the service when its checker
        maps onto a device engine; None = not serviceable (caller runs the
        direct path).  Deadlines reuse the test's ``checker_budget_s`` —
        the same knob check_safe honors — so budget semantics don't fork
        between the direct and serviced paths.

        A composed checker (the shape every suite builds: stats +
        workload + perf) routes per child: serviceable children submit to
        the service, the rest run directly, and Compose's own merge /
        concurrency / budget semantics apply unchanged."""
        from jepsen_tpu.checker.core import Compose
        from jepsen_tpu.checker.linearizable import Linearizable
        if isinstance(checker, Compose):
            if not any(self._routable(c) for c in checker.checkers.values()):
                return None
            shim = Compose(
                {n: _ServiceRouted(self, c) if self._routable(c) else c
                 for n, c in checker.checkers.items()},
                budget_s=checker.budget_s)
            return shim.check(test, history, opts)
        budget = (opts or {}).get("budget_s") \
            or (test or {}).get("checker_budget_s")
        inner = checker
        from jepsen_tpu.independent import IndependentChecker
        if isinstance(checker, IndependentChecker):
            inner = checker.inner
        if isinstance(inner, Linearizable):
            jm = inner._jax_model()
            if jm is None or inner.algorithm not in (None, "tpu"):
                return None
            req = self.submit(history, kind=KIND_WGL, model=jm,
                              deadline_s=budget,
                              **{k: v for k, v in inner.engine_opts.items()
                                 if k in ("capacity", "max_capacity")})
            return req.wait()
        try:
            from jepsen_tpu.checker.elle import ElleChecker
        except Exception:  # noqa: BLE001
            return None
        if isinstance(checker, ElleChecker):
            if checker.engine not in ("auto", "tpu"):
                return None
            req = self.submit(history, kind=KIND_ELLE,
                              workload=checker.workload,
                              realtime=checker.realtime,
                              consistency_models=checker.consistency_models,
                              deadline_s=checker.budget_s or budget)
            res = req.wait()
            from jepsen_tpu.elle import render
            render.write_artifacts(test, res, opts)
            return res
        return None

    def merged_trace(self, request_id) -> Optional[Dict[str, Any]]:
        """The merged trace payload of a completed request (``GET
        /trace/<request-id>`` and ``cli trace`` read this); None when
        the id is unknown or already evicted from the trace ring."""
        return self.metrics.find_trace(request_id)

    # -- lifecycle --------------------------------------------------------
    def queue_depth(self) -> int:
        return self._sched.depth()

    def alive(self) -> bool:
        """Liveness: the device loop is running and admissions are open."""
        return not self._closed and self._sched.alive()

    def ping(self) -> Dict[str, Any]:
        """The heartbeat payload: cheap, lock-light, never dispatches.
        The fleet's health checker and ``GET /healthz`` both read this."""
        from jepsen_tpu.engine.fission import fission_threshold
        return {"alive": self.alive(),
                "platform": self.platform,
                "queue-depth": self._sched.depth(),
                "inflight-cells": self._sched.inflight(),
                "inflight-requests": self._inflight(),
                # sizing advertisement: the capacity rung past which THIS
                # worker splits instead of escalating (docs/deployment.md
                # "Sizing fleet fission") — the fleet edge reads it to
                # sanity-check per-worker vs fleet-aggregate capacity
                "fission-threshold": fission_threshold()}

    def healthz(self) -> Dict[str, Any]:
        """Single-service health probe (the degenerate one-worker fleet
        view, so load balancers see ONE schema either way)."""
        p = self.ping()
        return {"ok": p["alive"], "workers": [
            {"worker": 0, "alive": p["alive"], "circuit": "closed",
             "queue-depth": p["queue-depth"],
             "inflight-cells": p["inflight-cells"]}]}

    def drain(self, timeout: Optional[float] = None) -> bool:
        return self._sched.drain(timeout=timeout)

    def kill(self) -> list:
        """Abrupt shutdown (worker-crash semantics, no drain): stop the
        loop, evict and return the still-queued cells unresolved.  The
        fleet reroutes them; in-flight requests hang until a sibling's
        hedge resolves them — exactly a crashed process's behaviour."""
        with self._lock:
            self._closed = True
        return self._sched.kill()

    def close(self, timeout: Optional[float] = None) -> bool:
        """Stop admitting, drain the queue (every admitted request still
        resolves), stop the device loop."""
        with self._lock:
            if self._closed:
                return True
            self._closed = True
        return self._sched.stop(drain=True, timeout=timeout)

    def __enter__(self) -> "CheckService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
