"""The out-of-process worker tier (serve/transport, serve/worker_main,
fleet.ProcFleet) and its wire protocol.

Covers the length-prefixed frame codec and its edge cases (clean EOF,
torn header, partial payload at a cut, oversized rejection before the
payload is read), duplicate-delivery idempotency at both ends (worker
RESULT cache + client ``claim_finish``), deadline-expiry on arrival,
the single-winner journal-recovery claim (atomic_io.exclusive_create,
stale-pid steal), and the ProcFleet supervisor loop (partition →
reroute, mid-frame cut → re-dial, worker kill → respawn) — all on the
ThreadWorker tier so tier-1 CI exercises the identical protocol over
real sockets without process-spawn latency.  One ``slow``-marked test
runs the real SubprocessWorker end to end.
"""

import json
import os
import signal
import socket
import struct
import time
import urllib.request

import pytest

from jepsen_tpu.atomic_io import exclusive_create
from jepsen_tpu.control.retry import RetryPolicy
from jepsen_tpu.nemesis.registry import FaultRegistry
from jepsen_tpu.net_proxy import PairProxy
from jepsen_tpu.serve import CheckService
from jepsen_tpu.serve.chaos import ChaosNemesis
from jepsen_tpu.serve.fleet import FleetJournal, ProcFleet
from jepsen_tpu.serve.transport import (
    ConnectionLost, F_ERROR, F_HEALTHZ, F_RESULT, F_SUBMIT, FrameError,
    MAX_FRAME_BYTES, OversizedFrame, ProcWorkerService, RemoteCall,
    encode_frame, read_frame,
)
from jepsen_tpu.serve.worker_main import ThreadWorker
from jepsen_tpu.synth import cas_register_history, corrupt_reads

QUICK = RetryPolicy(tries=2, backoff_s=0.01, max_backoff_s=0.05)


def clean_history(n=30, seed=0):
    return cas_register_history(n, concurrency=3, seed=seed)


def broken_history(n=30, seed=0):
    return corrupt_reads(cas_register_history(n, concurrency=3, seed=seed),
                         n=1, seed=seed)


# ---------------------------------------------------------------------------
# frame codec
# ---------------------------------------------------------------------------


class TestFraming:
    def _pair(self):
        a, b = socket.socketpair()
        a.settimeout(5)
        b.settimeout(5)
        return a, b

    def test_round_trip(self):
        a, b = self._pair()
        frame = {"type": "status", "id": "s1", "n": [1, 2, 3]}
        a.sendall(encode_frame(frame))
        assert read_frame(b) == frame
        a.close(), b.close()

    def test_clean_eof_is_none(self):
        a, b = self._pair()
        a.close()
        assert read_frame(b) is None   # peer closed at a frame boundary
        b.close()

    def test_torn_header_is_frame_error(self):
        a, b = self._pair()
        a.sendall(b"\x00\x00")         # 2 of 4 header bytes, then cut
        a.close()
        with pytest.raises(FrameError):
            read_frame(b)
        b.close()

    def test_partial_payload_at_cut_is_frame_error(self):
        a, b = self._pair()
        buf = encode_frame({"type": "status", "id": "x"})
        a.sendall(buf[:len(buf) - 3])  # header + most of the payload
        a.close()
        with pytest.raises(FrameError):
            read_frame(b)
        b.close()

    def test_oversized_rejected_before_payload(self):
        a, b = self._pair()
        a.sendall(struct.pack(">I", MAX_FRAME_BYTES + 1))
        with pytest.raises(OversizedFrame):
            read_frame(b)              # raises on the header alone
        a.close(), b.close()

    def test_oversized_encode_raises_client_side(self):
        with pytest.raises(OversizedFrame):
            encode_frame({"type": "submit", "id": "big",
                          "blob": "x" * 256}, max_frame=64)

    def test_untyped_frame_is_frame_error(self):
        a, b = self._pair()
        payload = json.dumps({"id": "no-type"}).encode()
        a.sendall(struct.pack(">I", len(payload)) + payload)
        with pytest.raises(FrameError):
            read_frame(b)
        a.close(), b.close()


class TestRemoteCall:
    def test_duplicate_delivery_is_structural_noop(self):
        call = RemoteCall(clean_history(10), "wgl", {})
        assert call.deliver({"valid": True}) is True
        # a late duplicate RESULT (reconnect redelivery) cannot
        # double-finish or overwrite: claim_finish admits exactly one
        assert call.deliver({"valid": False}) is False
        assert call.result["valid"] is True


# ---------------------------------------------------------------------------
# recovery claim
# ---------------------------------------------------------------------------


class TestRecoveryClaim:
    def test_exclusive_create_first_wins(self, tmp_path):
        p = str(tmp_path / "claim")
        assert exclusive_create(p, "a") is True
        assert exclusive_create(p, "b") is False
        with open(p) as f:
            assert f.read() == "a"

    def test_claim_first_wins_and_is_idempotent(self, tmp_path):
        d = str(tmp_path)
        assert FleetJournal.claim_recovery(d, "alpha") is True
        assert FleetJournal.claim_recovery(d, "beta") is False
        assert FleetJournal.claim_recovery(d, "alpha") is True  # re-entry
        assert FleetJournal.claim_holder(d)["claimant"] == "alpha"

    def test_stale_claim_with_dead_pid_is_stolen(self, tmp_path):
        d = str(tmp_path)
        path = FleetJournal._claim_path(d)
        with open(path, "w") as f:
            # max pid is bounded well below 2**22 +  a margin; this pid
            # cannot be a live process
            json.dump({"claimant": "ghost", "pid": 2 ** 22 + 1}, f)
        assert FleetJournal.claim_recovery(d, "necromancer") is True
        assert FleetJournal.claim_holder(d)["claimant"] == "necromancer"
        assert os.path.exists(path + ".stale")  # the corpse is kept


# ---------------------------------------------------------------------------
# the wire server (ThreadWorker: identical protocol, no spawn latency)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def wire():
    """One protocol worker behind a PairProxy link, plus its facade."""
    launcher = ThreadWorker(
        "w0", lambda: CheckService(max_lanes=8, capacity=32))
    proxy = PairProxy("test", "w0", ("127.0.0.1", 1))
    svc = ProcWorkerService(launcher, proxy, retry_policy=QUICK,
                            name="w0")
    yield svc
    svc.close(timeout=10.0)
    proxy.close()


def _raw_conn(wire):
    """A bare protocol client straight at the worker's real port,
    bypassing the facade (and the proxy) to hand-craft frames."""
    s = socket.create_connection(("127.0.0.1",
                                  wire.launcher.await_ready()), timeout=10)
    s.settimeout(10)
    return s


def _submit_frame(cid, history, rem=30.0):
    return {"type": F_SUBMIT, "id": cid, "kind": "wgl",
            "spec": {"model": "cas-register"}, "deadline-rem-s": rem,
            "ops": [op.to_dict() for op in history]}


class TestWireWorker:
    def test_submit_parity_over_the_wire(self, wire):
        assert wire.check(clean_history(seed=1),
                          kind="wgl", model="cas-register",
                          deadline_s=60.0)["valid"] is True
        assert wire.check(broken_history(seed=2),
                          kind="wgl", model="cas-register",
                          deadline_s=60.0)["valid"] is False

    def test_ping_and_healthz_over_the_wire(self, wire):
        ping = wire.ping()
        assert ping["alive"] and ping["reachable"]
        assert wire.healthz()["ok"]
        # the worker says which platform it actually got (conftest: cpu)
        assert ping["platform"] == "cpu"
        assert wire.launcher.status()["platform"] == "cpu"

    def test_duplicate_submit_same_id_runs_once(self, wire):
        s = _raw_conn(wire)
        frame = _submit_frame("dup-1", clean_history(20, seed=3))
        s.sendall(encode_frame(frame))
        seen, results = [], []
        while len(results) < 1:
            f = read_frame(s)
            seen.append(f["type"])
            if f["type"] == F_RESULT:
                results.append(f)
        s.sendall(encode_frame(frame))     # byte-identical duplicate
        f = read_frame(s)
        assert f["type"] == "ack" and f.get("dup") is True
        f = read_frame(s)                  # cached verdict, re-delivered
        assert f["type"] == F_RESULT and f["id"] == "dup-1"
        assert f["result"]["valid"] == results[0]["result"]["valid"]
        s.close()

    def test_deadline_expired_on_arrival(self, wire):
        s = _raw_conn(wire)
        s.sendall(encode_frame(
            _submit_frame("late-1", clean_history(10, seed=4), rem=0.0)))
        frames = [read_frame(s), read_frame(s)]
        res = [f for f in frames if f["type"] == F_RESULT][0]
        assert res["result"]["valid"] == "unknown"  # expired, not checked
        s.close()

    def test_oversized_frame_gets_error_and_poisons_conn(self, wire):
        s = _raw_conn(wire)
        s.sendall(struct.pack(">I", MAX_FRAME_BYTES + 1) + b"junk")
        f = read_frame(s)
        assert f["type"] == F_ERROR
        assert "oversized" in f["error"].lower() or "frame" in f["error"]
        # the stream is unparseable past an oversized header: the worker
        # hangs up rather than resynchronize (FIN at the boundary, or an
        # RST when the unread payload is still in its receive buffer)
        try:
            assert read_frame(s) is None
        except (ConnectionResetError, FrameError):
            pass
        s.close()

    def test_partial_frame_cut_then_fresh_conn_works(self, wire):
        s = _raw_conn(wire)
        buf = encode_frame(_submit_frame("torn-1", clean_history(10, seed=5)))
        s.sendall(buf[:len(buf) // 2])
        s.close()                          # mid-frame cut
        # the worker drops that conn only; a fresh dial works at once
        assert wire.check(clean_history(10, seed=5), kind="wgl",
                          model="cas-register",
                          deadline_s=60.0)["valid"] is True

    def test_partition_raises_then_heal_recovers(self, wire):
        wire.proxy.sever()
        with pytest.raises(ConnectionLost):
            wire.submit(clean_history(10, seed=6), kind="wgl",
                        model="cas-register", deadline_s=5.0)
        wire.proxy.heal()
        assert wire.check(clean_history(10, seed=6), kind="wgl",
                          model="cas-register",
                          deadline_s=60.0)["valid"] is True

    def test_mid_frame_reset_then_resubmit(self, wire):
        wire.proxy.reset_conns()           # RST every live proxied conn
        assert wire.check(clean_history(10, seed=7), kind="wgl",
                          model="cas-register",
                          deadline_s=60.0)["valid"] is True


# ---------------------------------------------------------------------------
# ProcFleet (spawn=False): supervisor + chaos link faults, tier-1 speed
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def procfleet():
    with ProcFleet(workers=2, spawn=False, max_lanes=8, capacity=32,
                   default_deadline_s=60.0, supervise_s=0.2) as f:
        yield f


@pytest.fixture()
def chaos(procfleet):
    c = ChaosNemesis(procfleet, registry=FaultRegistry())
    yield c
    c.heal_all()


class TestProcFleet:
    def test_verdict_parity(self, procfleet):
        assert procfleet.check(clean_history(seed=10), kind="wgl",
                               model="cas-register")["valid"] is True
        assert procfleet.check(broken_history(seed=11), kind="wgl",
                               model="cas-register")["valid"] is False

    def test_partition_reroutes_then_heals(self, procfleet, chaos):
        key = chaos.partition_worker(0)
        res = procfleet.check(clean_history(seed=12), kind="wgl",
                              model="cas-register")
        assert res["valid"] is True        # rerouted around the dead link
        chaos.heal(key)
        assert procfleet.healthz(deep=True)["ok"]

    def test_cut_links_recovers(self, procfleet, chaos):
        chaos.cut_links(1)
        assert procfleet.check(clean_history(seed=13), kind="wgl",
                               model="cas-register")["valid"] is True

    def test_killed_worker_is_respawned(self, procfleet):
        before = procfleet.metrics.snapshot()["counters"].get(
            "supervisor-respawns", 0)
        procfleet.workers[0].service.kill()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            snap = procfleet.metrics.snapshot()["counters"]
            if snap.get("supervisor-respawns", 0) > before:
                break
            time.sleep(0.1)
        assert procfleet.metrics.snapshot()["counters"].get(
            "supervisor-respawns", 0) > before
        assert procfleet.check(clean_history(seed=14), kind="wgl",
                               model="cas-register")["valid"] is True

    def test_healthz_deep_interrogates_remotes(self, procfleet):
        hz = procfleet.healthz(deep=True)
        assert hz["ok"]
        assert all(w.get("remote", {}).get("ok") for w in hz["workers"])

    def test_scheduler_faults_refused_on_proc_workers(self, procfleet,
                                                      chaos):
        with pytest.raises(ValueError):
            chaos.pause_worker(0)          # another process's scheduler

    def test_web_healthz_deep(self, procfleet):
        import threading

        from jepsen_tpu.web import serve as web_serve
        httpd = web_serve(base="store", port=0, block=False,
                          service=procfleet)
        th = threading.Thread(target=httpd.serve_forever, daemon=True)
        th.start()
        try:
            port = httpd.server_address[1]
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz?deep=1",
                    timeout=10) as r:
                body = json.loads(r.read())
            assert body["ok"]
            assert all(w.get("remote", {}).get("ok")
                       for w in body["workers"])
        finally:
            httpd.shutdown()
            httpd.server_close()


# ---------------------------------------------------------------------------
# the real thing: worker processes (slow tier)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestSubprocessFleet:
    def test_spawn_kill_respawn_parity(self, tmp_path):
        with ProcFleet(workers=2, spawn=True, max_lanes=8, capacity=32,
                       default_deadline_s=120.0, supervise_s=0.25,
                       log_dir=str(tmp_path)) as f:
            assert f.check(clean_history(seed=20), kind="wgl",
                           model="cas-register",
                           deadline_s=120.0)["valid"] is True
            # the ready line names the platform the child really got
            assert f.workers[0].service.launcher.status()[
                "platform"] == "cpu"
            pid = f.workers[0].service.launcher.proc.pid
            os.kill(pid, signal.SIGKILL)
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                c = f.metrics.snapshot()["counters"]
                if c.get("supervisor-respawns", 0) >= 1:
                    break
                time.sleep(0.25)
            assert f.metrics.snapshot()["counters"].get(
                "supervisor-respawns", 0) >= 1
            new_pid = f.workers[0].service.launcher.proc.pid
            assert new_pid != pid
            assert f.check(broken_history(seed=21), kind="wgl",
                           model="cas-register",
                           deadline_s=120.0)["valid"] is False
