"""The fault-tolerant serving fleet (jepsen_tpu.serve.fleet/router/chaos).

Covers the router primitives (circuit breaker state machine, health
EWMAs, rendezvous hashing and its minimal-remap property), the fleet
facade (verdict parity with a single CheckService, worker kill/poison
recovery, hedging, the admission-vs-deadline race), the in-flight
journal (record/complete, crash recovery, explicit expiry — never
silently dropped, never fabricated), and the web ``/healthz`` surface.
Everything runs on the CPU backend.
"""

import json
import threading
import urllib.error
import urllib.request

import pytest

from jepsen_tpu.engine import ladder
from jepsen_tpu.history import History
from jepsen_tpu.nemesis.registry import FaultRegistry
from jepsen_tpu.serve import CheckService
from jepsen_tpu.serve.chaos import ChaosNemesis
from jepsen_tpu.serve.decompose import decompose
from jepsen_tpu.serve.fleet import (
    MIN_WORKER_LANES, Fleet, FleetJournal, worker_lane_share,
)
from jepsen_tpu.serve.request import Request
from jepsen_tpu.serve.router import (
    CLOSED, CircuitBreaker, HALF_OPEN, OPEN, Router, WorkerHealth,
    rendezvous_score,
)
from jepsen_tpu.serve.service import build_spec
from jepsen_tpu.synth import cas_register_history, corrupt_reads


def keyed_history(n_keys=3, n_ops=30, seed=0) -> History:
    """An independent-workload history: per-key cas histories wrapped in
    (key, value) tuples, processes disjoint per key — decomposes into
    n_keys cells, each rendezvous-routed by its own key."""
    ops = []
    for k in range(n_keys):
        h = cas_register_history(n_ops, concurrency=3, seed=seed + k)
        for op in h:
            ops.append(op.with_(process=op.process + 10 * k,
                                value=(k, op.value)))
    return History(ops, reindex=True)


def _fleet_meta(res):
    """The routing metadata, wherever aggregation put it: top-level for
    single-cell requests, per-key under ``results`` for decomposed ones."""
    if "fleet" in res:
        return res["fleet"]
    for r in (res.get("results") or {}).values():
        if r and "fleet" in r:
            return r["fleet"]
    return None


@pytest.fixture(scope="module")
def fleet():
    with Fleet(workers=3, max_lanes=16, capacity=64, hedge_s=0.5,
               default_deadline_s=60.0) as f:
        yield f


# ---------------------------------------------------------------------------
# router primitives
# ---------------------------------------------------------------------------


class TestCircuitBreaker:
    def test_opens_after_consecutive_failures(self):
        cb = CircuitBreaker(fail_threshold=3)
        for _ in range(2):
            cb.record_failure()
        assert cb.state == CLOSED and cb.allow()
        cb.record_failure()
        assert cb.state == OPEN
        assert not cb.allow()
        assert cb.transitions["opened"] == 1

    def test_success_resets_the_count(self):
        cb = CircuitBreaker(fail_threshold=2)
        cb.record_failure()
        cb.record_success()
        cb.record_failure()
        assert cb.state == CLOSED  # never two consecutive

    def test_half_open_probe_then_close(self):
        t = [0.0]
        cb = CircuitBreaker(fail_threshold=1, open_s=1.0,
                            clock=lambda: t[0])
        cb.record_failure()
        assert not cb.allow()                  # still cooling down
        t[0] = 1.5
        assert cb.allow()                      # the single probe
        assert cb.state == HALF_OPEN
        assert not cb.allow()                  # probe slot is claimed
        cb.record_success()
        assert cb.state == CLOSED
        assert cb.transitions["half-opened"] == 1
        assert cb.transitions["closed"] == 1

    def test_failed_probe_reopens(self):
        t = [0.0]
        cb = CircuitBreaker(fail_threshold=3, open_s=1.0,
                            clock=lambda: t[0])
        for _ in range(3):
            cb.record_failure()
        t[0] = 1.5
        assert cb.allow()
        cb.record_failure()                    # ONE probe failure reopens,
        assert cb.state == OPEN                # threshold does not apply
        assert not cb.allow()
        t[0] = 2.0
        assert not cb.allow()                  # fresh cooldown from reopen
        t[0] = 2.6
        assert cb.allow()

    def test_reset(self):
        cb = CircuitBreaker(fail_threshold=1)
        cb.record_failure()
        cb.reset()
        assert cb.state == CLOSED and cb.allow()


class TestWorkerHealth:
    def test_ewma_tracks_latency_and_errors(self):
        h = WorkerHealth(alpha=0.5)
        h.observe(latency_s=1.0)
        h.observe(latency_s=2.0)
        snap = h.snapshot()
        assert snap["latency-ewma-s"] == pytest.approx(1.5)
        assert snap["error-ewma"] == 0.0
        h.observe(error=True)
        assert h.snapshot()["error-ewma"] == pytest.approx(0.5)

    def test_heartbeat_age(self):
        h = WorkerHealth()
        assert h.snapshot()["last-beat-age-s"] is None
        h.beat()
        snap = h.snapshot()
        assert snap["heartbeats"] == 1
        assert snap["last-beat-age-s"] is not None


class _FakeWorker:
    def __init__(self, wid, alive=True):
        self.wid = wid
        self._alive = alive
        self.breaker = CircuitBreaker(fail_threshold=1)

    def alive(self):
        return self._alive


class TestRendezvous:
    def test_deterministic_across_processes(self):
        # blake2b, not hash(): the score must not depend on the process's
        # string-hash salt (a restarted fleet must rank identically)
        assert rendezvous_score("wgl:5", "0") \
            == rendezvous_score("wgl:5", "0")
        assert rendezvous_score("wgl:5", "0") \
            != rendezvous_score("wgl:5", "1")

    def test_death_remaps_only_the_dead_workers_keys(self):
        workers = [_FakeWorker(i) for i in range(4)]
        router = Router(workers)
        tokens = [f"wgl:{k}" for k in range(64)]
        before = {t: router.pick(t).wid for t in tokens}
        workers[2]._alive = False
        after = {t: router.pick(t).wid for t in tokens}
        for t in tokens:
            if before[t] != 2:
                assert after[t] == before[t]   # survivors keep their keys
            else:
                assert after[t] != 2
        assert any(before[t] == 2 for t in tokens)

    def test_open_circuit_falls_to_sibling(self):
        workers = [_FakeWorker(i) for i in range(3)]
        router = Router(workers)
        token = "wgl:7"
        first = router.pick(token)
        first.breaker.record_failure()         # threshold 1: open
        second = router.pick(token)
        assert second is not None and second.wid != first.wid

    def test_no_worker_available(self):
        workers = [_FakeWorker(0, alive=False), _FakeWorker(1)]
        workers[1].breaker.record_failure()
        router = Router(workers)
        assert router.pick("wgl:1") is None


class TestWorkerLaneShare:
    def test_rounds_up_onto_the_solo_ladder(self):
        # ceil(64/3)=22 -> 32: the same pow2 rung a solo service uses,
        # so fleet and oracle share compiled-engine cache entries
        assert worker_lane_share(64, 3) == 32
        assert worker_lane_share(64, 1) == 64
        assert worker_lane_share(64, 64) == MIN_WORKER_LANES
        assert worker_lane_share(4096, 1) == ladder.MAX_LANE_BUCKET


# ---------------------------------------------------------------------------
# the fleet facade
# ---------------------------------------------------------------------------


class TestFleetParity:
    def test_verdicts_match_single_service(self, fleet):
        good = cas_register_history(40, concurrency=4, seed=1)
        bad = corrupt_reads(cas_register_history(40, concurrency=4,
                                                 seed=2), n=1, seed=2)
        keyed = keyed_history(n_keys=3, n_ops=30, seed=9)
        with CheckService(max_lanes=16, capacity=64) as solo:
            for h in (good, bad, keyed):
                a = solo.check(h, kind="wgl", model="cas-register")
                b = fleet.check(h, kind="wgl", model="cas-register")
                assert b["valid"] == a["valid"]
        res = fleet.check(good, kind="wgl", model="cas-register")
        meta = _fleet_meta(res)
        assert meta is not None and "worker" in meta
        assert res["serve"]["cells"] >= 1

    def test_concurrent_clients(self, fleet):
        out = [None] * 8

        def client(i):
            h = cas_register_history(30, concurrency=3, seed=40 + i)
            out[i] = fleet.check(h, kind="wgl",
                                 model="cas-register")["valid"]

        ts = [threading.Thread(target=client, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert out == [True] * 8

    def test_expired_resolves_unknown_never_false(self, fleet):
        bad = corrupt_reads(cas_register_history(50, seed=3), n=2, seed=3)
        res = fleet.check(bad, kind="wgl", model="cas-register",
                          deadline_s=0.0)
        assert res["valid"] == "unknown"

    def test_admission_race_backpressure_vs_expiry(self):
        # queue-full + deadline expiring while blocked: the request must
        # surface unknown — never dropped, never false, never an exception
        f = Fleet(workers=1, max_queue_cells=0, max_lanes=8,
                  default_deadline_s=60.0)
        try:
            req = f.submit(cas_register_history(10, seed=4), kind="wgl",
                           model="cas-register", block=True, deadline_s=0.2)
            assert req.done()
            assert req.wait(timeout=5)["valid"] == "unknown"
            c = f.metrics.snapshot()["counters"]
            assert c["deadline-expired"] >= 1
            assert c["requests-completed"] >= 1
            assert c.get("requests-rejected", 0) == 0
        finally:
            f.close(timeout=30.0)


class TestFleetChaos:
    def test_kill_reroutes_to_siblings(self, fleet):
        chaos = ChaosNemesis(fleet, registry=FaultRegistry())
        chaos.kill_worker(0)
        try:
            reqs = [fleet.submit(cas_register_history(30, seed=50 + s),
                                 kind="wgl", model="cas-register")
                    for s in range(4)]
            assert [r.wait(timeout=120)["valid"] for r in reqs] \
                == [True] * 4
        finally:
            assert chaos.heal_all() == {"fleet:kill:0": "healed"}
        assert fleet.workers[0].alive()
        assert fleet.workers[0].generation >= 1

    def test_poison_never_fabricates_false(self, fleet):
        # both dispatch tiers of one worker fail: every verdict must come
        # from a healthy sibling, and the poisoned worker's circuit opens
        chaos = ChaosNemesis(fleet, registry=FaultRegistry())
        chaos.poison_dispatch(1)
        try:
            good = [fleet.submit(cas_register_history(30, seed=60 + s),
                                 kind="wgl", model="cas-register")
                    for s in range(4)]
            bad = fleet.submit(
                corrupt_reads(cas_register_history(40, seed=65), n=1,
                              seed=65), kind="wgl", model="cas-register")
            assert [r.wait(timeout=120)["valid"] for r in good] \
                == [True] * 4
            assert bad.wait(timeout=120)["valid"] is False
        finally:
            chaos.heal_all()
        fleet.workers[1].breaker.reset()   # don't leak an open circuit

    def test_pause_is_covered_by_hedge(self, fleet):
        # a stalled worker (stall >> hedge_s=0.5) must not stall its
        # requests: the hedge resolves them on a sibling.  Routing is
        # hash-spread, so whether any given request lands on the paused
        # worker is seed-dependent — the invariant asserted is that ALL
        # resolve True regardless.
        chaos = ChaosNemesis(fleet, registry=FaultRegistry())
        chaos.pause_worker(2, stall_s=3.0)
        try:
            reqs = [fleet.submit(cas_register_history(30, seed=70 + s),
                                 kind="wgl", model="cas-register",
                                 deadline_s=30.0)
                    for s in range(6)]
            assert [r.wait(timeout=120)["valid"] for r in reqs] \
                == [True] * 6
        finally:
            chaos.heal_all()

    def test_healthz_reflects_circuit_and_death(self):
        f = Fleet(workers=2, max_lanes=8, pin_devices=False)
        try:
            hz = f.healthz()
            assert hz["ok"] and len(hz["workers"]) == 2
            assert all(w["circuit"] == CLOSED for w in hz["workers"])
            f.workers[0].kill()
            hz = f.healthz()
            assert hz["ok"]                    # one survivor suffices
            assert not hz["workers"][0]["alive"]
            f.workers[1].kill()
            assert not f.healthz()["ok"]
        finally:
            f.kill()

    def test_single_service_healthz_same_schema(self):
        with CheckService(max_lanes=8) as svc:
            hz = svc.healthz()
            assert hz["ok"] is True
            assert hz["workers"][0]["circuit"] == CLOSED


# ---------------------------------------------------------------------------
# the in-flight journal
# ---------------------------------------------------------------------------


def _journaled_request(history, deadline_s=None):
    req = Request(history, "wgl", build_spec("wgl", model="cas-register"),
                  deadline_s=deadline_s)
    cells = decompose(req)
    for i, c in enumerate(cells):
        c.cid = f"{req.id}.{i}"
    return req, cells


class TestJournal:
    def test_record_and_complete(self, tmp_path):
        j = FleetJournal(str(tmp_path / "j"))
        req, cells = _journaled_request(cas_register_history(20, seed=5))
        j.record(req, cells)
        assert j.pending_count() == len(cells)
        on_disk = json.loads((tmp_path / "j" / j.FILENAME).read_text())
        assert set(on_disk["pending"]) == {c.cid for c in cells}
        for c in cells:
            j.complete(c.cid)
        assert j.pending_count() == 0
        assert json.loads(
            (tmp_path / "j" / j.FILENAME).read_text())["pending"] == {}

    def test_recover_pending_round_trips(self, tmp_path):
        j = FleetJournal(str(tmp_path / "j"))
        h = cas_register_history(20, seed=6)
        req, cells = _journaled_request(h, deadline_s=120.0)
        j.record(req, cells)
        rec = FleetJournal.recover(str(tmp_path / "j"))
        assert len(rec["pending"]) == len(cells) and not rec["expired"]
        item = rec["pending"][0]
        assert len(item["history"]) == len(h)
        assert item["kwargs"]["kind"] == "wgl"
        assert item["kwargs"]["model"] == "cas-register"
        assert 0 < item["kwargs"]["deadline_s"] <= 120.0

    def test_recover_classifies_spent_deadlines_as_expired(self, tmp_path):
        # a cell journaled with its budget already spent must surface in
        # "expired" — recovery never invents deadline headroom
        j = FleetJournal(str(tmp_path / "j"))
        req, cells = _journaled_request(cas_register_history(20, seed=7),
                                        deadline_s=-1.0)
        j.record(req, cells)
        rec = FleetJournal.recover(str(tmp_path / "j"))
        assert not rec["pending"]
        assert len(rec["expired"]) == len(cells)
        assert rec["expired"][0]["kwargs"]["deadline_s"] == 0.0

    def test_recover_missing_journal_is_empty(self, tmp_path):
        rec = FleetJournal.recover(str(tmp_path / "nope"))
        assert rec == {"pending": [], "expired": []}

    def test_crash_recovery_end_to_end(self, tmp_path):
        # a journal left behind by a crashed fleet (built directly here,
        # so the test is deterministic — the live crash-mid-campaign path
        # is scripts/fleet_chaos_smoke.py phase B) re-enqueues onto a
        # fresh fleet and every cell re-checks to a real verdict
        j = FleetJournal(str(tmp_path / "j1"))
        for s in range(3):
            req, cells = _journaled_request(
                cas_register_history(20, seed=80 + s), deadline_s=300.0)
            j.record(req, cells)
        with Fleet(workers=1, journal_dir=str(tmp_path / "j2"),
                   max_lanes=8, pin_devices=False) as f2:
            rec = f2.resubmit_recovered(str(tmp_path / "j1"))
            assert len(rec["requests"]) == 3 and not rec["expired"]
            for req in rec["requests"]:
                assert req.wait(timeout=120)["valid"] is True
            assert f2.metrics.snapshot()["counters"][
                "journal-recovered"] == 3


# ---------------------------------------------------------------------------
# web surface
# ---------------------------------------------------------------------------


class TestHealthzEndpoint:
    def test_healthz_over_http(self, tmp_path):
        from jepsen_tpu.web import serve
        f = Fleet(workers=2, max_lanes=8, pin_devices=False)
        httpd = serve(base=str(tmp_path), port=0, block=False, service=f)
        th = threading.Thread(target=httpd.serve_forever, daemon=True)
        th.start()
        url = f"http://127.0.0.1:{httpd.server_address[1]}/healthz"
        try:
            with urllib.request.urlopen(url) as r:
                body = json.loads(r.read())
            assert r.status == 200 and body["ok"]
            assert len(body["workers"]) == 2
            assert {"worker", "alive", "circuit", "queue-depth"} \
                <= set(body["workers"][0])
            f.workers[0].kill()
            f.workers[1].kill()
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(url)
            assert ei.value.code == 503
            assert not json.loads(ei.value.read())["ok"]
        finally:
            httpd.shutdown()
            f.kill()

    def test_healthz_without_service(self, tmp_path):
        from jepsen_tpu.web import serve
        httpd = serve(base=str(tmp_path), port=0, block=False)
        th = threading.Thread(target=httpd.serve_forever, daemon=True)
        th.start()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{httpd.server_address[1]}"
                    f"/healthz") as r:
                assert json.loads(r.read()) == {"ok": True, "workers": []}
        finally:
            httpd.shutdown()


class TestWorkersSnapshotRace:
    """Regression pin for the Warden RACE01 fix on ``Fleet.workers``:
    ``add_worker`` appends to the slot list under the fleet lock, but
    the heartbeat/supervisor/export paths used to iterate the live
    list.  They now go through ``workers_snapshot()``; this scales up
    concurrently with status reads and demands internally-consistent
    views throughout."""

    def test_concurrent_scale_up_and_status(self):
        with Fleet(workers=1, max_lanes=8, capacity=16,
                   default_deadline_s=60.0, pin_devices=False) as f:
            stop = threading.Event()
            errors = []

            def reader():
                while not stop.is_set():
                    try:
                        snap = f.workers_snapshot()
                        # a snapshot is a point-in-time copy: wids are
                        # exactly 0..n-1 in append order, never torn
                        assert [w.wid for w in snap] == \
                            list(range(len(snap)))
                        st = f.fleet_status()
                        assert len(st["workers"]) >= 1
                        f.healthz()
                    except Exception as e:  # noqa: BLE001 — collected
                        errors.append(e)
                        return

            readers = [threading.Thread(target=reader) for _ in range(3)]
            for t in readers:
                t.start()
            added = [f.add_worker() for _ in range(4)]
            stop.set()
            for t in readers:
                t.join()
            assert not errors, errors
            assert [w.wid for w in f.workers_snapshot()] == \
                list(range(1 + len(added)))
            # the snapshot is a copy — mutating it cannot corrupt the
            # fleet's own slot list
            snap = f.workers_snapshot()
            snap.clear()
            assert len(f.workers_snapshot()) == 1 + len(added)
