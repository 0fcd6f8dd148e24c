"""The worker side of the wire: a CheckService behind a socket, and the
process launchers that put it there.

``python -m jepsen_tpu.serve.worker_main`` is the entrypoint a
:class:`~jepsen_tpu.serve.fleet.ProcFleet` supervisor spawns per worker
slot: it builds one local :class:`~jepsen_tpu.serve.service.CheckService`,
wraps it in a :class:`WorkerServer` speaking the serve/transport.py frame
protocol, prints one ``{"ready": true, "port": N, "pid": P, "platform":
"cpu"|"tpu"}`` line on stdout (the launcher's readiness handshake, naming
the JAX platform the worker actually got), and serves until SIGTERM.

Three layers live here:

- :class:`WorkerServer` — the protocol server: accepts connections,
  dedups SUBMIT ids (live requests re-attach to the new connection,
  finished ones re-deliver the cached RESULT — the worker half of the
  exactly-once story), re-anchors ``deadline-rem-s`` on its own
  monotonic clock (already-spent deadlines resolve ``unknown``
  immediately, no dispatch), and answers STATUS/HEALTHZ/DRAIN RPCs.
  A torn frame (mid-frame cut) drops that connection and nothing else;
  an oversized frame is answered with an ERROR frame, then the poisoned
  stream is closed.
- :class:`SubprocessWorker` — control/util-style daemon management for
  a real OS worker process: spawn in its own session (``setsid``
  discipline, so kill() can SIGKILL the whole group), readiness
  handshake with a deadline, stderr to a per-worker log file, SIGTERM →
  SIGKILL escalation on terminate.
- :class:`ThreadWorker` — the same protocol server over a real socket
  but hosting the CheckService in-process: the tier-1 test vehicle.
  Every frame, dedup path, and fault behaves identically; only the
  process boundary is elided, so CI exercises the wire without paying
  subprocess + JAX-warmup tax per test.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import select
import signal
import socket
import subprocess
import sys
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional

from jepsen_tpu.clock import mono_now
from jepsen_tpu.history import History
from jepsen_tpu.serve.aggregate import expired_result
from jepsen_tpu.serve.request import Request
from jepsen_tpu.serve.service import (CheckService, ServiceClosed,
                                      ServiceSaturated)
from jepsen_tpu.obs.telemetry import telemetry_interval_s
from jepsen_tpu.serve.auth import (AuthError, fleet_token, sign_frame,
                                   verify_frame)
from jepsen_tpu.serve.registry import parse_mesh
from jepsen_tpu.serve.transport import (F_ACK, F_DRAIN, F_ERROR, F_HEALTHZ,
                                        F_REGISTER, F_REPLY, F_RESULT,
                                        F_STATUS, F_SUBMIT, F_TELEMETRY,
                                        FrameError, MAX_FRAME_BYTES,
                                        OversizedFrame, TransportError,
                                        WireClient, encode_frame,
                                        read_frame)

log = logging.getLogger("jepsen.serve.worker")

#: finished-request RESULT cache depth: how far back a reconnecting
#: client can ask for a verdict it may have missed.  Bounded so a
#: long-lived worker cannot leak memory one finished cell at a time.
RESULT_CACHE = 1024


class _Conn:
    """One accepted connection: the socket plus a per-connection send
    lock so concurrent RESULT pushes and RPC replies interleave at frame
    boundaries, never mid-frame."""

    def __init__(self, sock: socket.socket,
                 token: Optional[str] = None):
        self.sock = sock
        self.token = token  # outbound frames are signed when set
        self._send_lock = threading.Lock()
        self.open = True

    def send(self, frame: Dict[str, Any], max_frame: int) -> bool:
        data = encode_frame(sign_frame(frame, self.token), max_frame)
        with self._send_lock:
            if not self.open:
                return False
            try:
                self.sock.sendall(data)
                return True
            except OSError:
                self.open = False
                return False

    def close(self) -> None:
        with self._send_lock:
            self.open = False
        try:
            self.sock.close()
        except OSError:
            pass


class WorkerServer:
    """Serve one CheckService over the frame protocol."""

    def __init__(self, service: CheckService, host: str = "127.0.0.1",
                 port: int = 0, max_frame: int = MAX_FRAME_BYTES,
                 telemetry_s: Optional[float] = None,
                 token: Optional[str] = None):
        self.service = service
        self.max_frame = max_frame
        # frame auth (serve/auth.py): with a configured fleet token,
        # every inbound frame must verify or the connection is answered
        # with a typed ERROR and hung up.  The token is held, used for
        # mac computation, and NEVER logged or exported.
        self._token = token if token is not None else fleet_token()
        self._lock = threading.Lock()  # inflight/done/conn tables
        self._inflight: Dict[str, Request] = {}
        self._done: "OrderedDict[str, Dict[str, Any]]" = OrderedDict()
        self._conn_for: Dict[str, _Conn] = {}
        self._conns: List[_Conn] = []
        self._closed = False
        self._last_idle = mono_now()
        self._t0 = mono_now()
        # Watchtower push cadence: None = the env-configured default;
        # <= 0 disables the push thread entirely
        self.telemetry_s = (telemetry_interval_s() if telemetry_s is None
                            else float(telemetry_s))
        self._tele_stop = threading.Event()
        self._tele_seq = 0
        sched = getattr(service, "_sched", None)
        if sched is not None and hasattr(sched, "add_idle_listener"):
            sched.add_idle_listener(self._note_idle)
        self._srv = socket.socket()
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind((host, port))
        self._srv.listen(64)
        self.port = self._srv.getsockname()[1]
        threading.Thread(target=self._accept_loop, daemon=True,
                         name=f"worker-accept-{self.port}").start()
        if self.telemetry_s > 0:
            threading.Thread(target=self._telemetry_loop, daemon=True,
                             name=f"worker-tele-{self.port}").start()

    def _note_idle(self) -> None:
        with self._lock:
            self._last_idle = mono_now()

    # -- telemetry push ----------------------------------------------------
    def telemetry_payload(self) -> Dict[str, Any]:
        """One TELEMETRY frame body: process identity plus the full
        metrics snapshot minus the trace ring (traces are bulky and
        already travel on RESULT frames)."""
        snap = dict(self.service.metrics.snapshot())
        snap.pop("traces", None)
        self._tele_seq += 1
        return {"pid": os.getpid(),
                "uptime-s": round(mono_now() - self._t0, 3),
                "seq": self._tele_seq,
                "interval-s": self.telemetry_s,
                "metrics": snap}

    def _telemetry_loop(self) -> None:
        """Push the payload to every open connection on the cadence.
        Best-effort by design: a dead connection drops the frame (its
        reader cleanup already prunes the conn table), and the *absence*
        of pushes is itself the signal — the fleet-side TelemetryStore
        flags this worker stale after 2 missed intervals."""
        while not self._tele_stop.wait(timeout=self.telemetry_s):
            with self._lock:
                if self._closed:
                    return
                conns = list(self._conns)
            if not conns:
                continue
            try:
                frame = {"type": F_TELEMETRY,
                         "payload": self.telemetry_payload()}
                for conn in conns:
                    conn.send(frame, self.max_frame)
            except Exception:  # noqa: BLE001 — a torn snapshot must not
                log.debug("telemetry push failed", exc_info=True)

    # -- accept/read -------------------------------------------------------
    def _accept_loop(self) -> None:
        while True:
            try:
                sock, _ = self._srv.accept()
            except OSError:
                return  # listener closed
            try:
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            except OSError:
                pass
            conn = _Conn(sock, token=self._token)
            with self._lock:
                if self._closed:
                    conn.close()
                    continue
                self._conns.append(conn)
            threading.Thread(target=self._serve_conn, args=(conn,),
                             daemon=True,
                             name=f"worker-conn-{self.port}").start()

    def _serve_conn(self, conn: _Conn) -> None:
        try:
            while True:
                try:
                    frame = read_frame(conn.sock, self.max_frame)
                except OversizedFrame as e:
                    # answer, then close: the stream is poisoned (the
                    # oversized payload was never consumed)
                    conn.send({"type": F_ERROR, "id": None,
                               "error": str(e),
                               "error-class": "OversizedFrame"},
                              self.max_frame)
                    return
                except (FrameError, OSError):
                    # torn frame / RST: a mid-frame cut kills this
                    # connection only — in-flight requests keep running
                    # and re-deliver on the client's next connection
                    return
                if frame is None:
                    return  # clean close
                if not verify_frame(frame, self._token):
                    # auth fail-closed: typed ERROR, then hangup.  The
                    # message names the failure mode only — never the
                    # token or the mac (serve/auth.py discipline).
                    what = ("unauthenticated frame"
                            if not isinstance(frame.get("auth"), str)
                            else "bad frame mac")
                    conn.send({"type": F_ERROR, "id": frame.get("id"),
                               "error": f"{what} rejected",
                               "error-class": "AuthError"},
                              self.max_frame)
                    return
                self._dispatch(conn, frame)
        finally:
            conn.close()
            with self._lock:
                if conn in self._conns:
                    self._conns.remove(conn)

    def _dispatch(self, conn: _Conn, frame: Dict[str, Any]) -> None:
        ftype = frame.get("type")
        try:
            if ftype == F_SUBMIT:
                self._handle_submit(conn, frame)
            elif ftype == F_STATUS:
                self._reply(conn, frame, self._status_payload(frame))
            elif ftype == F_HEALTHZ:
                self._reply(conn, frame, self.service.healthz())
            elif ftype == F_DRAIN:
                threading.Thread(
                    target=self._handle_drain, args=(conn, frame),
                    daemon=True).start()
            else:
                conn.send({"type": F_ERROR, "id": frame.get("id"),
                           "error": f"unknown frame type {ftype!r}",
                           "error-class": "FrameError"}, self.max_frame)
        except Exception as e:  # noqa: BLE001 — one bad frame must not
            log.exception("worker frame dispatch failed")  # kill the conn
            conn.send({"type": F_ERROR, "id": frame.get("id"),
                       "error": f"{type(e).__name__}: {e}",
                       "error-class": type(e).__name__}, self.max_frame)

    # -- SUBMIT ------------------------------------------------------------
    def _handle_submit(self, conn: _Conn, frame: Dict[str, Any]) -> None:
        cid = str(frame.get("id"))
        with self._lock:
            cached = self._done.get(cid)
            live = self._inflight.get(cid)
            if live is not None:
                # duplicate of a running SUBMIT (client re-sent across a
                # reconnect): re-attach its RESULT to this connection
                self._conn_for[cid] = conn
        trace = frame.get("trace")
        if cached is not None:
            # duplicate of a FINISHED submit: ack + re-deliver the cached
            # verdict — the client's claim_finish makes a true duplicate
            # delivery a no-op, so resending is always safe
            conn.send({"type": F_ACK, "id": cid, "dup": True,
                       "trace": trace}, self.max_frame)
            conn.send({"type": F_RESULT, "id": cid, "result": cached,
                       "trace": trace}, self.max_frame)
            return
        if live is not None:
            conn.send({"type": F_ACK, "id": cid, "dup": True,
                       "trace": trace}, self.max_frame)
            return
        kind = frame.get("kind") or "wgl"
        rem = frame.get("deadline-rem-s")
        if rem is not None and float(rem) <= 0:
            # spent before arrival: resolve unknown without a dispatch —
            # the deadline authority is the sender's remaining figure,
            # re-anchored here, never a wall clock comparison
            res = expired_result(kind)
            self._remember(cid, res)
            conn.send({"type": F_ACK, "id": cid, "trace": trace},
                      self.max_frame)
            conn.send({"type": F_RESULT, "id": cid, "result": res,
                       "trace": trace}, self.max_frame)
            return
        history = History(frame.get("ops") or [])
        spec = dict(frame.get("spec") or {})
        try:
            # the propagated trace context makes the worker-side request
            # a child span of the sender's; span times re-anchor on THIS
            # process's monotonic clock at submit
            req = self.service.submit(
                history, kind=kind, block=False,
                deadline_s=float(rem) if rem is not None else None,
                trace=trace, **spec)
        except (ServiceSaturated, ServiceClosed) as e:
            conn.send({"type": F_ERROR, "id": cid, "error": str(e),
                       "error-class": type(e).__name__}, self.max_frame)
            return
        with self._lock:
            self._inflight[cid] = req
            self._conn_for[cid] = conn
        conn.send({"type": F_ACK, "id": cid, "trace": trace},
                  self.max_frame)
        threading.Thread(target=self._await_result, args=(cid, req),
                         daemon=True,
                         name=f"worker-wait-{cid}").start()

    def _await_result(self, cid: str, req: Request) -> None:
        try:
            result = req.wait(timeout=None)
        except Exception as e:  # noqa: BLE001 — degrade, never fabricate
            result = {"valid": "unknown", "analyzer": "worker",
                      "error": f"worker wait failed: "
                               f"{type(e).__name__}: {e}"}
        self._finish(cid, result)

    def _remember(self, cid: str, result: Dict[str, Any]) -> None:
        with self._lock:
            self._done[cid] = result
            while len(self._done) > RESULT_CACHE:
                self._done.popitem(last=False)

    def _finish(self, cid: str, result: Dict[str, Any]) -> None:
        with self._lock:
            self._inflight.pop(cid, None)
            self._done[cid] = result
            while len(self._done) > RESULT_CACHE:
                self._done.popitem(last=False)
            conn = self._conn_for.pop(cid, None)
        if conn is not None:
            # best-effort push; a client that missed it (cut link) will
            # re-SUBMIT the same id and hit the _done cache.  The frame
            # carries the trace ids alongside the serve payload so every
            # RESULT is self-identifying on the wire.
            serve = (result or {}).get("serve") or {}
            trace = ({"trace-id": serve.get("trace-id"),
                      "parent-span-id": serve.get("parent-span-id")}
                     if serve.get("trace-id") else None)
            conn.send({"type": F_RESULT, "id": cid, "result": result,
                       "trace": trace}, self.max_frame)

    # -- RPCs --------------------------------------------------------------
    def _status_payload(
            self, frame: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        p = dict(self.service.ping())
        with self._lock:
            p["wire-inflight"] = len(self._inflight)
            p["wire-done-cached"] = len(self._done)
            last_idle = self._last_idle
        p["idle-age-s"] = round(mono_now() - last_idle, 3)
        p["pid"] = os.getpid()
        if frame and frame.get("recorder") is not None:
            # runtime arm/disarm of this process's flight recorder — the
            # worker half of POST /recorder
            from jepsen_tpu.obs.recorder import RECORDER
            if frame.get("recorder"):
                RECORDER.enable()
            else:
                RECORDER.disable()
            p["recorder"] = RECORDER.stats()
        if frame and frame.get("metrics"):
            # the fleet-wide scrape: full Metrics.snapshot() on demand
            # over the same STATUS frame the heartbeat already uses
            p["metrics"] = self.service.metrics.snapshot()
        return p

    def _reply(self, conn: _Conn, frame: Dict[str, Any],
               payload: Any) -> None:
        out = {"type": F_REPLY, "id": frame.get("id"), "payload": payload}
        if frame.get("trace") is not None:  # context echo, wire symmetry
            out["trace"] = frame.get("trace")
        conn.send(out, self.max_frame)

    def _handle_drain(self, conn: _Conn, frame: Dict[str, Any]) -> None:
        t = frame.get("timeout-s")
        ok = self.service.drain(timeout=t)
        self._reply(conn, frame, bool(ok))

    # -- lifecycle ---------------------------------------------------------
    def alive(self) -> bool:
        with self._lock:
            if self._closed:
                return False
        return self.service.alive()

    def close(self) -> None:
        self._tele_stop.set()
        with self._lock:
            self._closed = True
        try:
            self._srv.close()
        except OSError:
            pass

    def kill(self) -> None:
        """Crash semantics: listener down, live connections RST (clients
        see a hard cut, not a graceful close), service killed."""
        self.close()
        with self._lock:
            conns, self._conns = self._conns, []
        for c in conns:
            try:
                c.sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                                  b"\x01\x00\x00\x00\x00\x00\x00\x00")
            except OSError:
                pass
            c.close()
        self.service.kill()


# ---------------------------------------------------------------------------
# launchers
# ---------------------------------------------------------------------------


class SubprocessWorker:
    """One real worker OS process, managed with the control/util daemon
    discipline: own session (killable as a group), readiness handshake
    on stdout, stderr to a log file, SIGTERM → SIGKILL escalation."""

    def __init__(self, name: str, log_path: str, *,
                 args: Optional[Dict[str, Any]] = None,
                 env: Optional[Dict[str, str]] = None,
                 ready_timeout_s: float = 120.0):
        self.name = name
        self.log_path = log_path
        self.ready_timeout_s = ready_timeout_s
        self.port: Optional[int] = None
        self.platform: Optional[str] = None  # from the ready line
        # where a client dials this worker back.  A wildcard bind
        # (0.0.0.0/::) is not dialable; local supervision reaches it on
        # loopback, remote fleets advertise a real host via REGISTER.
        bind = (args or {}).get("host")
        self.host = ("127.0.0.1" if bind in (None, "", "0.0.0.0", "::")
                     else str(bind))
        argv = [sys.executable, "-m", "jepsen_tpu.serve.worker_main"]
        for k, v in (args or {}).items():
            if v is None:
                continue
            argv += [f"--{k}", str(v)]
        root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        penv = dict(os.environ)
        penv["PYTHONPATH"] = root + os.pathsep + penv.get("PYTHONPATH", "")
        # Nothing assigns a worker process a chip yet (ROADMAP R1), and a
        # chip belongs to one process, so workers run on XLA-CPU unless
        # the parent's environment names a platform.  The ready line and
        # status() report the platform the worker actually got.
        penv.setdefault("JAX_PLATFORMS", "cpu")
        penv.update(env or {})
        self._log = open(log_path, "ab")
        self.proc = subprocess.Popen(
            argv, stdout=subprocess.PIPE, stderr=self._log,
            cwd=root, env=penv,
            start_new_session=True)  # own group: kill() nukes descendants

    def await_ready(self) -> int:
        """Block until the worker prints its ready line; returns the real
        port it listens on.  Raises if the process dies or stalls first."""
        if self.port is not None:
            return self.port
        out = self.proc.stdout
        deadline = mono_now() + self.ready_timeout_s
        buf = b""
        while b"\n" not in buf:
            left = deadline - mono_now()
            if left <= 0:
                raise TimeoutError(
                    f"worker {self.name} not ready after "
                    f"{self.ready_timeout_s:.0f}s (log: {self.log_path})")
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"worker {self.name} exited rc={self.proc.returncode} "
                    f"before ready (log: {self.log_path})")
            r, _, _ = select.select([out], [], [], min(0.5, left))
            if r:
                chunk = os.read(out.fileno(), 4096)
                if not chunk:
                    raise RuntimeError(
                        f"worker {self.name} closed stdout before ready "
                        f"(log: {self.log_path})")
                buf += chunk
        line = buf.split(b"\n", 1)[0]
        msg = json.loads(line.decode("utf-8"))
        if not msg.get("ready"):
            raise RuntimeError(f"worker {self.name} bad ready line: {msg}")
        self.port = int(msg["port"])
        self.platform = msg.get("platform")
        return self.port

    def alive(self) -> bool:
        return self.proc.poll() is None

    def kill(self) -> None:
        """Crash the worker: SIGKILL its whole process group."""
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError, OSError):
            pass
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass
        self._close_log()

    def terminate(self, timeout_s: float = 10.0) -> None:
        """Graceful stop: SIGTERM (the worker closes its service), then
        SIGKILL the group if it hangs."""
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGTERM)
            except (ProcessLookupError, PermissionError, OSError):
                pass
            try:
                self.proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                self.kill()
                return
        self._close_log()

    def _close_log(self) -> None:
        try:
            self._log.close()
        except OSError:
            pass

    def status(self) -> Dict[str, Any]:
        return {"kind": "subprocess", "pid": self.proc.pid,
                "alive": self.alive(), "port": self.port,
                "platform": self.platform, "log": self.log_path}


class ThreadWorker:
    """The protocol server over a real socket, CheckService in-process:
    identical wire behavior to :class:`SubprocessWorker` minus the
    process boundary.  Tier-1 tests and ``ProcFleet(spawn=False)`` use
    this so the frame/dedup/fault paths run on CPU CI in milliseconds."""

    def __init__(self, name: str, make_service, *,
                 max_frame: int = MAX_FRAME_BYTES,
                 telemetry_s: Optional[float] = None):
        self.name = name
        self.host = "127.0.0.1"  # in-process: always loopback-dialable
        self.service = make_service()
        self.server = WorkerServer(self.service, max_frame=max_frame,
                                   telemetry_s=telemetry_s)
        self._killed = False

    def await_ready(self) -> int:
        return self.server.port

    def alive(self) -> bool:
        return not self._killed and self.server.alive()

    def kill(self) -> None:
        self._killed = True
        self.server.kill()

    def terminate(self, timeout_s: float = 10.0) -> None:
        self._killed = True
        self.server.close()
        self.service.close(timeout=timeout_s)

    def status(self) -> Dict[str, Any]:
        return {"kind": "thread", "pid": os.getpid(),
                "alive": self.alive(), "port": self.server.port,
                "platform": self.service.platform}


# ---------------------------------------------------------------------------
# fleet registration (the worker side of serve/fleetport.py)
# ---------------------------------------------------------------------------


class FleetRegistration:
    """Register this worker with a fleetport and keep its lease alive.

    The worker dials the fleet (not the other way around) exactly once
    per incarnation: a REGISTER frame carries its dial-back address,
    device inventory, mesh shape, and capability buckets; the REPLY
    brings back the slot id and the lease duration.  From then on the
    renewal loop pushes *named* TELEMETRY frames at a third of the lease
    — the same frames Watchtower already aggregates double as
    heartbeats, so there is no separate keepalive protocol to keep
    honest.

    Failure discipline mirrors the verdict discipline: a transport cut
    degrades (re-register with backoff — the fleet treats a comeback
    after eviction as a new generation), but an :class:`AuthError` is
    **permanent** — a worker holding the wrong token must not hammer
    the control plane with frames it can never authenticate."""

    def __init__(self, server: WorkerServer, *,
                 fleet_addr, name: str,
                 advertise_host: str, port: Optional[int] = None,
                 mesh: Any = (1,), devices=(), buckets=(),
                 token: Optional[str] = None):
        self.server = server
        self.name = name
        self.host = advertise_host
        self.port = int(port if port is not None else server.port)
        self.mesh = parse_mesh(mesh)
        self.devices = tuple(devices)
        self.buckets = tuple(buckets)
        self.wid: Optional[int] = None
        self.lease_s: float = 10.0
        self.registrations = 0
        self.rejected = False  # permanent auth rejection
        self.registered = threading.Event()
        self._stop = threading.Event()
        self._client = WireClient(tuple(fleet_addr),
                                  name=f"fleet@{fleet_addr[0]}",
                                  token=token)
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "FleetRegistration":
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"fleet-reg-{self.name}")
        self._thread.start()
        return self

    def wait_registered(self, timeout: Optional[float] = None) -> bool:
        return self.registered.wait(timeout=timeout)

    def _register(self) -> None:
        reply = self._client.call(
            F_REGISTER,
            {"name": self.name, "host": self.host, "port": self.port,
             "pid": os.getpid(), "devices": list(self.devices),
             "mesh": "x".join(str(d) for d in self.mesh),
             "buckets": list(self.buckets)},
            timeout_s=10.0) or {}
        self.wid = reply.get("wid")
        lease = reply.get("lease-s")
        if lease:
            self.lease_s = float(lease)
        self.registrations += 1
        self.registered.set()

    def _loop(self) -> None:
        backoff = 0.2
        joined = False
        while not self._stop.is_set():
            try:
                if not joined:
                    self._register()
                    joined = True
                    backoff = 0.2
                # the renewal IS a telemetry frame — sent as an RPC so a
                # refusal is observable: the fleetport replies REPLY to a
                # member, and a typed ERROR ("NotRegistered") to an
                # evicted name, which lands here as a TransportError and
                # drives the re-register below
                self._client.call(
                    F_TELEMETRY,
                    {"name": self.name,
                     "payload": self.server.telemetry_payload()},
                    timeout_s=max(self.lease_s / 2.0, 1.0))
            except AuthError:
                # wrong/missing token: permanent — stop, never retry.
                # The log line names the condition, never the token.
                log.error("fleet registration rejected: auth failure")
                self.rejected = True
                return
            except (TransportError, OSError) as e:
                # cut link / refused dial / torn frame: transient —
                # re-register next round (the fleet sees a comeback as
                # a new generation if the lease lapsed meanwhile)
                log.warning("fleet link lost (%s); re-registering",
                            type(e).__name__)
                joined = False
                self._stop.wait(timeout=backoff)
                backoff = min(backoff * 2, 2.0)
                continue
            self._stop.wait(timeout=max(self.lease_s / 3.0, 0.05))

    def stop(self) -> None:
        self._stop.set()
        self._client.close()
        if self._thread is not None:
            self._thread.join(timeout=5.0)


# ---------------------------------------------------------------------------
# entrypoint
# ---------------------------------------------------------------------------


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="jepsen_tpu.serve.worker_main",
        description="one fleet worker: a CheckService behind the wire")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--max-lanes", type=int, default=64)
    ap.add_argument("--max-queue", type=int, default=4096)
    ap.add_argument("--capacity", type=int, default=None)
    ap.add_argument("--max-capacity", type=int, default=None)
    ap.add_argument("--max-frame", type=int, default=MAX_FRAME_BYTES)
    ap.add_argument("--telemetry-s", type=float, default=None,
                    help="TELEMETRY push cadence in seconds (default: "
                         "JEPSEN_TPU_TELEMETRY_S or 1.0; <= 0 disables)")
    ap.add_argument("--name", default=None,
                    help="worker name to register under (default: "
                         "worker-<pid>)")
    ap.add_argument("--fleet-addr", default=None, metavar="HOST:PORT",
                    help="register with the fleetport at this address "
                         "and hold a lease there")
    ap.add_argument("--advertise-host", default=None,
                    help="dial-back host to advertise in REGISTER "
                         "(required sense when binding 0.0.0.0; "
                         "default: --host, or 127.0.0.1 on a wildcard "
                         "bind)")
    ap.add_argument("--mesh", default="1",
                    help="device-mesh shape to advertise, e.g. 4x2 "
                         "(default: the degenerate 1-mesh)")
    args = ap.parse_args(argv)
    logging.basicConfig(
        level=logging.INFO, stream=sys.stderr,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    svc_kw: Dict[str, Any] = dict(max_lanes=args.max_lanes,
                                  max_queue_cells=args.max_queue)
    if args.capacity is not None:
        svc_kw["capacity"] = args.capacity
    if args.max_capacity is not None:
        svc_kw["max_capacity"] = args.max_capacity
    service = CheckService(**svc_kw)
    server = WorkerServer(service, host=args.host, port=args.port,
                          max_frame=args.max_frame,
                          telemetry_s=args.telemetry_s)
    registration: Optional[FleetRegistration] = None
    if args.fleet_addr:
        fhost, _, fport = args.fleet_addr.rpartition(":")
        adv = args.advertise_host or (
            "127.0.0.1" if args.host in ("0.0.0.0", "::") else args.host)
        registration = FleetRegistration(
            server, fleet_addr=(fhost or "127.0.0.1", int(fport)),
            name=args.name or f"worker-{os.getpid()}",
            advertise_host=adv, mesh=args.mesh,
            buckets=("wgl", "elle")).start()
    stop = threading.Event()

    def _on_signal(signum, frame):  # noqa: ARG001 — signal signature
        stop.set()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    print(json.dumps({"ready": True, "port": server.port,
                      "pid": os.getpid(),
                      "platform": service.platform}), flush=True)
    while not stop.is_set():
        # the wait is the whole main thread's job; everything else runs
        # on the accept/conn/waiter threads
        stop.wait(timeout=1.0)
    if registration is not None:
        registration.stop()
    server.close()
    service.close(timeout=30.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
