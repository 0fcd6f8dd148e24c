"""The persistent batched checking service (jepsen_tpu.serve).

Covers the decomposer, the shape-bucket ladder, the continuous-batch
scheduler (parity with the direct checkers, concurrent submission,
deadlines, admission control, shutdown), core.analyze service routing,
the metrics surface, the web endpoints, and the satellite knobs (bounded
engine LRU, configurable independent workers, shared compile-cache
init).  Everything runs on the CPU backend.
"""

import json
import threading
import urllib.error
import urllib.request

import pytest

from jepsen_tpu import core
from jepsen_tpu.checker import Stats, wgl_cpu
from jepsen_tpu.checker.elle import ElleChecker
from jepsen_tpu.checker.linearizable import Linearizable
from jepsen_tpu.engine import ladder
from jepsen_tpu.history import History, INFO, NEMESIS, Op
from jepsen_tpu.independent import (
    DEFAULT_WORKERS, IndependentChecker, history_keys, subhistory,
    worker_count,
)
from jepsen_tpu.models import CASRegister, get_model
from jepsen_tpu.serve import (
    CheckService, ServiceClosed, ServiceSaturated,
)
from jepsen_tpu.serve.decompose import decompose
from jepsen_tpu.serve.request import Request
from jepsen_tpu.synth import (
    cas_register_history, corrupt_list_append, corrupt_reads,
    list_append_history,
)


def keyed_history(n_keys=3, n_ops=40, seed=0) -> History:
    """An independent-workload history: per-key cas histories wrapped in
    (key, value) tuples, processes disjoint per key."""
    ops = []
    for k in range(n_keys):
        h = cas_register_history(n_ops, concurrency=3, seed=seed + k)
        for op in h:
            ops.append(op.with_(process=op.process + 10 * k,
                                value=(k, op.value)))
    return History(ops, reindex=True)


@pytest.fixture(scope="module")
def svc():
    # The engine cache (and its miss counter) is process-global; record
    # where it stood when this module's service came up so assertions on
    # "recompiles" measure THIS module, not whichever test files ran
    # earlier in the same process.
    from jepsen_tpu.engine.cache import engine_cache_stats
    baseline = engine_cache_stats()["misses"]
    with CheckService(max_lanes=16) as s:
        s.test_recompile_baseline = baseline
        yield s


class TestBuckets:
    def test_pow2_ladder(self):
        assert ladder.pow2_at_least(1, 64) == 64
        assert ladder.pow2_at_least(64, 64) == 64
        assert ladder.pow2_at_least(65, 64) == 128
        assert ladder.pow2_at_least(300, 64) == 512

    def test_wgl_bucket_floor(self):
        h = cas_register_history(30, concurrency=3, seed=1)
        ev, w = ladder.wgl_bucket(h)
        assert ev == 64 and w == 8

    def test_width_bucket_counts_open_ops(self):
        h = cas_register_history(400, concurrency=20, seed=2)
        assert ladder.width_bucket(h) >= 16

    def test_elle_bucket_floor(self):
        h = list_append_history(10, seed=3)
        assert ladder.elle_bucket(h) == (32,)

    def test_lane_bucket(self):
        assert ladder.lane_bucket(1) == 1
        assert ladder.lane_bucket(3) == 4
        assert ladder.lane_bucket(9999) == ladder.MAX_LANE_BUCKET


class TestDecompose:
    def test_single_key_one_cell(self):
        h = cas_register_history(40, seed=4)
        req = Request(h, "wgl", {"model": get_model("cas-register")})
        cells = decompose(req)
        assert len(cells) == 1 and cells[0].key is None
        assert req.cells is cells

    def test_multi_key_splits(self):
        h = keyed_history(n_keys=3, seed=5)
        req = Request(h, "wgl", {"model": get_model("cas-register")})
        cells = decompose(req)
        assert [c.key for c in cells] == history_keys(h)
        # values unwrapped in the sub-histories
        assert all(not isinstance(op.value, tuple) or len(op.value) != 2
                   for c in cells for op in c.history)

    def test_multi_key_cells_equal_per_key_scans(self):
        # keys interleaved, a nemesis op ahead of every key and one ahead
        # of the last key only: the cells are what history_keys + one
        # subhistory scan per key give (key order, histories, buckets)
        per_key = [[op.with_(process=op.process + 10 * k,
                             value=(k, op.value))
                    for op in cas_register_history(30 + 20 * k,
                                                   concurrency=3, seed=40 + k)]
                   for k in (2, 0, 1)]
        nem = Op(process=NEMESIS, type=INFO, f="start")
        ops = [nem]
        for i in range(max(map(len, per_key))):
            if i == 5:
                ops.append(nem.with_(f="stop"))
            ops.extend(ks[i] for ks in per_key[:2 if i < 5 else 3]
                       if i < len(ks))
        h = History(ops, reindex=True)
        req = Request(h, "wgl", {"model": get_model("cas-register")})
        cells = decompose(req)
        assert [c.key for c in cells] == history_keys(h) == [2, 0, 1]
        for c in cells:
            want = subhistory(c.key, h)
            assert c.history == want
            assert [o.index for o in c.history] == list(range(len(want)))
            assert c.bucket[2:] == ladder.wgl_bucket(want)
            assert c.request is req
            assert [o.f for o in c.history if o.process == NEMESIS] == [
                "start", "stop"]
        assert cells[2].history[1].f == "stop" != cells[0].history[1].f

    def test_partially_keyed_never_splits(self):
        h = cas_register_history(40, seed=6)
        mixed = History(
            [op.with_(value=(0, op.value)) if op.index % 2 else op
             for op in h], reindex=True)
        req = Request(mixed, "wgl", {"model": get_model("cas-register")})
        assert len(decompose(req)) == 1

    def test_elle_one_cell(self):
        h = list_append_history(20, seed=7)
        req = Request(h, "elle", {"workload": "list-append",
                                  "realtime": False})
        cells = decompose(req)
        assert len(cells) == 1
        assert cells[0].bucket[0] == "elle"


class TestServiceParity:
    def test_wgl_matches_cpu_oracle(self, svc):
        hs = [cas_register_history(60, concurrency=4, seed=s)
              for s in range(4)]
        hs.append(corrupt_reads(hs[0], n=1, seed=9))
        expect = [wgl_cpu.check(CASRegister(), h)["valid"] for h in hs]
        got = [svc.check(h, kind="wgl", model="cas-register")["valid"]
               for h in hs]
        assert got == expect and False in expect

    def test_elle_matches_direct_checker(self, svc):
        good = list_append_history(30, seed=10)
        bad = corrupt_list_append(list_append_history(30, seed=11),
                                  anomaly_p=0.5, seed=11)
        direct = ElleChecker(workload="list-append")
        for h in (good, bad):
            want = direct.check({}, h, {})["valid"]
            got = svc.check(h, kind="elle", workload="list-append")
            assert got["valid"] == want

    def test_multi_key_decomposed_verdict(self, svc):
        h = keyed_history(n_keys=3, seed=12)
        res = svc.check(h, kind="wgl", model="cas-register")
        assert res["valid"] is True
        assert res["key-count"] == 3
        assert sorted(res["results"]) == [str(k) for k in range(3)] or \
            sorted(res["results"]) == [0, 1, 2]

    def test_serve_metadata_attached(self, svc):
        h = cas_register_history(40, seed=13)
        res = svc.check(h, kind="wgl", model="cas-register")
        meta = res["serve"]
        names = [s["span"] for s in meta["spans"]]
        assert names[0] == "enqueue" and "verdict" in names
        assert meta["cells"] == 1


class TestConcurrentStress:
    def test_64_mixed_histories_4_threads(self, svc):
        wgl = [cas_register_history(50, concurrency=3, seed=s)
               for s in range(24)]
        wgl += [corrupt_reads(cas_register_history(50, concurrency=3,
                                                   seed=100 + s),
                              n=1, seed=s) for s in range(24)]
        elle = [list_append_history(20, seed=200 + s) for s in range(8)]
        elle += [corrupt_list_append(list_append_history(20, seed=300 + s),
                                     anomaly_p=0.5, seed=s)
                 for s in range(8)]
        jobs = ([("wgl", h) for h in wgl] + [("elle", h) for h in elle])
        assert len(jobs) == 64
        expect = [wgl_cpu.check(CASRegister(), h)["valid"] for h in wgl] \
            + [ElleChecker().check({}, h, {})["valid"] for h in elle]

        results = [None] * len(jobs)

        def client(span):
            for i in span:
                kind, h = jobs[i]
                results[i] = svc.check(
                    h, kind=kind,
                    **({"model": "cas-register"} if kind == "wgl"
                       else {"workload": "list-append"}))

        threads = [threading.Thread(target=client,
                                    args=(range(j, len(jobs), 4),))
                   for j in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert all(r is not None for r in results)
        assert [r["valid"] for r in results] == expect

        snap = svc.metrics.snapshot()
        assert snap["counters"]["requests-completed"] >= 64
        assert snap["occupancy"]["lanes-used"] > 0
        assert snap["engine-cache"]["recompiles"] >= 1
        # bucketing holds recompiles far below the request count (the
        # megabatch path adds its own step/harvest/reset program family
        # per bucket shape on top of the barrier engines)
        assert (snap["engine-cache"]["recompiles"]
                - svc.test_recompile_baseline) < 48


class TestDeadlines:
    def test_expired_resolves_unknown_never_false(self, svc):
        # even a provably-broken history must not produce False after its
        # deadline: unknown is the only honest verdict for unchecked work
        bad = corrupt_reads(cas_register_history(50, seed=14), n=2, seed=14)
        res = svc.check(bad, kind="wgl", model="cas-register",
                        deadline_s=0.0)
        assert res["valid"] == "unknown"
        assert res.get("deadline-expired") is True
        assert svc.metrics.snapshot()["counters"]["deadline-expired"] >= 1

    def test_unexpired_deadline_still_checks(self, svc):
        h = cas_register_history(40, seed=15)
        res = svc.check(h, kind="wgl", model="cas-register",
                        deadline_s=120.0)
        assert res["valid"] is True


class TestLifecycle:
    def test_clean_shutdown_drains(self):
        svc = CheckService(max_lanes=8)
        reqs = [svc.submit(cas_register_history(40, seed=s),
                           kind="wgl", model="cas-register")
                for s in range(6)]
        assert svc.close(timeout=120.0)
        assert svc.queue_depth() == 0
        for r in reqs:  # every admitted request resolved
            assert r.done()
            assert r.wait(timeout=0)["valid"] is True

    def test_submit_after_close_raises(self):
        svc = CheckService(max_lanes=8)
        svc.close(timeout=30.0)
        with pytest.raises(ServiceClosed):
            svc.submit(cas_register_history(10, seed=16),
                       kind="wgl", model="cas-register")

    def test_admission_control_rejects(self):
        svc = CheckService(max_queue_cells=0, max_lanes=8)
        try:
            with pytest.raises(ServiceSaturated):
                svc.submit(cas_register_history(10, seed=17),
                           kind="wgl", model="cas-register", block=False)
            assert svc.metrics.snapshot()["counters"][
                "requests-rejected"] >= 1
        finally:
            svc.close(timeout=30.0)

    def test_admission_race_expiry_surfaces_unknown(self):
        # queue full AND the deadline expires while blocked on admission:
        # the request must come back already-done with unknown — not
        # dropped, not False, not ServiceSaturated, not a hang
        svc = CheckService(max_queue_cells=0, max_lanes=8)
        try:
            req = svc.submit(cas_register_history(10, seed=18),
                             kind="wgl", model="cas-register",
                             block=True, deadline_s=0.2)
            assert req.done()
            res = req.wait(timeout=5)
            assert res["valid"] == "unknown"
            assert res.get("deadline-expired") is True
            c = svc.metrics.snapshot()["counters"]
            assert c["deadline-expired"] >= 1
            assert c["requests-completed"] >= 1
            # expiry under backpressure is completion, not rejection
            assert c.get("requests-rejected", 0) == 0
        finally:
            svc.close(timeout=30.0)

    def test_context_manager(self):
        with CheckService(max_lanes=8) as svc:
            assert svc.check(cas_register_history(20, seed=18),
                             kind="wgl",
                             model="cas-register")["valid"] is True


class TestAnalyzeRouting:
    def _analyze_both(self, checker, history, tmp_path):
        direct = core.analyze({"name": "t", "checker": checker,
                               "store_dir": str(tmp_path / "d")}, history)
        with CheckService(max_lanes=8) as svc:
            routed = core.analyze({"name": "t", "checker": checker,
                                   "store_dir": str(tmp_path / "r"),
                                   "service": svc}, history)
        return direct, routed

    def test_linearizable_routes(self, tmp_path):
        h = cas_register_history(50, seed=19)
        direct, routed = self._analyze_both(
            Linearizable(get_model("cas-register")), h, tmp_path)
        assert routed["valid"] == direct["valid"] is True
        assert "serve" in routed and "serve" not in direct

    def test_independent_linearizable_routes(self, tmp_path):
        h = keyed_history(n_keys=2, seed=20)
        checker = IndependentChecker(Linearizable(get_model("cas-register")))
        direct, routed = self._analyze_both(checker, h, tmp_path)
        assert routed["valid"] == direct["valid"] is True
        assert routed["key-count"] == direct["key-count"] == 2

    def test_elle_routes(self, tmp_path):
        h = corrupt_list_append(list_append_history(30, seed=21),
                                anomaly_p=0.5, seed=21)
        direct, routed = self._analyze_both(ElleChecker(), h, tmp_path)
        assert routed["valid"] == direct["valid"] is False

    def test_composed_checker_routes_children(self, tmp_path):
        # the shape every suite builds: stats + device workload checker;
        # the workload child must route, stats must run directly
        from jepsen_tpu.checker import compose
        h = cas_register_history(40, seed=27)
        checker = compose({"stats": Stats(),
                           "workload": Linearizable(
                               get_model("cas-register"))})
        direct, routed = self._analyze_both(checker, h, tmp_path)
        assert routed["valid"] == direct["valid"] is True
        assert "serve" in routed["workload"]
        assert "serve" not in routed["stats"]
        assert routed["stats"]["valid"] is True

    def test_unserviceable_falls_back(self, tmp_path):
        h = cas_register_history(30, seed=22)
        direct, routed = self._analyze_both(Stats(), h, tmp_path)
        assert routed["valid"] == direct["valid"] is True
        assert "serve" not in routed  # direct path, no service metadata

    def test_run_tests_injects_service(self, tmp_path):
        tests = [{"name": f"svc-{i}", "store_base": str(tmp_path),
                  "nodes": [], "concurrency": 1,
                  "checker": Stats()} for i in range(2)]
        with CheckService(max_lanes=8) as svc:
            summary = core.run_tests(tests, workers=2, service=svc)
        assert [r["valid"] for r in summary["results"]] == [True, True]
        assert all(t.get("service") is svc for t in tests)


class TestWebEndpoints:
    @pytest.fixture()
    def server(self, tmp_path):
        from jepsen_tpu.web import serve
        svc = CheckService(max_lanes=8)
        httpd = serve(base=str(tmp_path), port=0, block=False, service=svc)
        th = threading.Thread(target=httpd.serve_forever, daemon=True)
        th.start()
        yield f"http://127.0.0.1:{httpd.server_address[1]}", svc
        httpd.shutdown()
        svc.close(timeout=30.0)

    def test_metrics_and_queue(self, server):
        url, svc = server
        svc.check(cas_register_history(30, seed=23), kind="wgl",
                  model="cas-register")
        snap = json.loads(urllib.request.urlopen(url + "/metrics").read())
        assert snap["counters"]["requests-completed"] >= 1
        assert "engine-cache" in snap and "gauges" in snap
        page = urllib.request.urlopen(url + "/queue").read().decode()
        assert "requests-submitted" in page

    def test_healthz_endpoint(self, server):
        url, svc = server
        body = json.loads(urllib.request.urlopen(url + "/healthz").read())
        assert body["ok"] is True
        w = body["workers"][0]
        assert w["circuit"] == "closed" and w["alive"] is True
        assert "queue-depth" in w
        svc.kill()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url + "/healthz")
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["ok"] is False

    def test_post_submit_round_trip(self, server):
        url, _ = server
        h = corrupt_reads(cas_register_history(40, seed=24), n=1, seed=24)
        body = {"ops": [op.to_dict() for op in h],
                "kind": "wgl", "model": "cas-register"}
        req = urllib.request.Request(
            url + "/submit", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        res = json.loads(urllib.request.urlopen(req).read())
        assert res["valid"] is False
        assert res["serve"]["request-id"] >= 0

    def test_post_submit_independent_rewraps(self, server):
        # a JSONL round-trip turns keyed (k, v) tuples into lists; the
        # independent flag restores them so the service splits per key
        url, _ = server
        h = keyed_history(n_keys=2, n_ops=15, seed=26)
        ops = [json.loads(json.dumps(op.to_dict())) for op in h]
        assert isinstance(ops[0]["value"], list)
        body = {"ops": ops, "kind": "wgl", "model": "cas-register",
                "independent": True}
        req = urllib.request.Request(
            url + "/submit", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        res = json.loads(urllib.request.urlopen(req).read())
        assert res["valid"] is True and res["key-count"] == 2

    def test_post_submit_bad_body_400(self, server):
        url, _ = server
        req = urllib.request.Request(
            url + "/submit", data=b"{\"nope\": 1}",
            headers={"Content-Type": "application/json"}, method="POST")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req)
        assert ei.value.code == 400


class TestMetricsSchema:
    """Pins the /metrics document shape dashboards scrape.  Renaming a
    top-level section or a seed counter is a breaking change to every
    consumer of the endpoint — these tests make that a deliberate edit,
    not an accident."""

    #: the exact top-level sections of Metrics.snapshot()
    SECTIONS = {"counters", "gauges", "occupancy", "histograms",
                "engine-cache", "megabatch", "flight-recorder", "traces",
                "fission", "queue", "tenants"}
    #: the counters seeded at construction (inc() may add more)
    SEED_COUNTERS = {"requests-submitted", "requests-completed",
                     "requests-rejected", "cells-submitted",
                     "cells-completed", "deadline-expired",
                     "dispatches", "host-fallbacks"}

    def test_snapshot_schema_pinned(self, svc):
        svc.check(cas_register_history(30, seed=31), kind="wgl",
                  model="cas-register")
        snap = svc.metrics.snapshot()
        assert set(snap) == self.SECTIONS
        assert set(snap["counters"]) >= self.SEED_COUNTERS
        # hist-merge-skipped: the fleet-scrape corruption counter
        # (obs/hist.py) surfaces in every snapshot
        assert "hist-merge-skipped" in snap["counters"]
        assert set(snap["gauges"]) == {"queue-depth", "inflight-requests",
                                       "compiles-per-1k-dispatches",
                                       "epochs-behind-live",
                                       "monitor-lag-epochs",
                                       "queue-oldest-wait-s"}
        # the Governor's wait-age input: per-bucket depths + oldest age
        assert {"depth", "buckets", "oldest-wait-s"} <= set(snap["queue"])
        assert isinstance(snap["tenants"], dict)
        # the steady-state compile gauge is a ratio (or None pre-dispatch)
        c1k = snap["gauges"]["compiles-per-1k-dispatches"]
        assert c1k is None or c1k >= 0.0
        assert {"lanes-used", "lanes-padded", "ratio",
                "dispatch-seconds"} <= set(snap["occupancy"])
        assert {"enabled", "capacity", "recorded", "buffered",
                "dropped"} == set(snap["flight-recorder"])
        # engine-cache routes through the shared jepsen_tpu.engine.cache
        # module: per-tag counts make the "singlev" family visible next
        # to "batchv"/"megav" (the stale-import satellite)
        assert "tags" in snap["engine-cache"]
        # fission: one merged section for the whole story — the engine
        # splitter counters (engine.fission), the shrink recursion's
        # (engine.shrink), Hydra's fleet-plane counters
        # (serve.fission_plane), and every tier's histograms
        assert {"checks", "splits", "recombines", "escalations",
                "shrink_checks", "shrink_probes", "shrink_refutes",
                "shrink_exhausted",
                "scattered", "remote-subproblems", "cancelled",
                "witness-recoveries", "witness-recovery-failures",
                "histograms"} <= set(snap["fission"])
        for h in snap["histograms"].values():
            assert {"count", "sum-s", "p50", "p90", "p99",
                    "buckets-us"} == set(h)

    def test_prometheus_exposition_schema(self, svc):
        """The /metrics.prom contract: every counter, gauge, and
        histogram in the snapshot appears in the text exposition under
        its mechanical ``metric_name`` mapping, and the whole document
        passes the line-format validator (grammar, label syntax,
        histogram bucket monotonicity).  A rename anywhere in the
        snapshot schema is therefore a test-visible act."""
        from jepsen_tpu.obs.prom import (metric_name, render_prom,
                                         validate_exposition)
        svc.check(cas_register_history(30, seed=32), kind="wgl",
                  model="cas-register")
        snap = svc.metrics.snapshot()
        text = render_prom(snap)
        families = validate_exposition(text)
        for name in snap["counters"]:
            assert metric_name("counter", name) in families
        for name, v in snap["gauges"].items():
            if v is not None:   # None gauges are deliberately unscraped
                assert metric_name("gauge", name) in families
        for name in snap["histograms"]:
            assert metric_name("histogram", name) in families
        # the merged fission section rides its own renderer: every tier's
        # counters surface as jepsen_tpu_fission_* (hyphens sanitized)
        for name in ("scattered", "shrink_probes", "witness-recoveries"):
            assert f"jepsen_tpu_fission_{name.replace('-', '_')}_total" \
                in families

    def test_concurrent_snapshots_never_tear_structurally(self, svc):
        """Gauges are point samples taken outside the metrics lock
        (metrics is the lock-order leaf; the depth/inflight callbacks
        take scheduler locks) — so a snapshot's gauges may reflect a
        later instant than its counters.  The contract pinned here:
        concurrent snapshots stay structurally whole and every counter
        is monotone across them; nothing asserts gauges reconcile with
        counters, because they deliberately may not (the documented
        tear in serve/metrics.py)."""
        stop = threading.Event()
        errors = []

        def submitter():
            i = 0
            while not stop.is_set() and i < 8:
                svc.submit(cas_register_history(20, seed=100 + i),
                           kind="wgl", model="cas-register")
                i += 1

        t = threading.Thread(target=submitter)
        t.start()
        last = {}
        try:
            for _ in range(25):
                snap = svc.metrics.snapshot()
                if set(snap) != TestMetricsSchema.SECTIONS:
                    errors.append(f"sections torn: {set(snap)}")
                for k, v in snap["counters"].items():
                    if v < last.get(k, 0):
                        errors.append(f"counter {k} went backwards")
                    last[k] = v
                for name, g in snap["gauges"].items():
                    if name == "compiles-per-1k-dispatches":
                        # a ratio gauge: None before the first dispatch,
                        # then a non-negative float
                        if g is not None and not (isinstance(g, float)
                                                  and g >= 0.0):
                            errors.append(f"compile gauge torn: {g}")
                    elif name == "queue-oldest-wait-s":
                        # a wall-age gauge: non-negative float seconds
                        if not isinstance(g, float) or g < 0.0:
                            errors.append(f"wait-age gauge torn: {g}")
                    elif not isinstance(g, int) or g < 0:
                        errors.append(f"gauge not a point sample: {g}")
        finally:
            stop.set()
            t.join(timeout=120)
        svc.drain(timeout=120)
        assert not errors, errors


class TestSatellites:
    def test_engine_lru_bounded_with_counters(self):
        from jepsen_tpu.engine.cache import EngineCache
        c = EngineCache(2)
        assert c.get("a") is None
        c.put("a", 1)
        c.put("b", 2)
        assert c.get("a") == 1  # refreshes recency
        c.put("c", 3)           # evicts b
        assert c.get("b") is None
        s = c.stats()
        assert s["capacity"] == 2 and s["size"] == 2
        assert s["hits"] == 1 and s["misses"] == 2 and s["evictions"] == 1

    def test_engine_cache_env_sizing(self, monkeypatch):
        from jepsen_tpu.engine import cache
        assert cache.CACHE.capacity >= 1
        assert set(cache.engine_cache_stats()) >= {
            "hits", "misses", "evictions", "size", "capacity"}

    def test_worker_count_resolution(self, monkeypatch):
        monkeypatch.delenv("JEPSEN_TPU_WORKERS", raising=False)
        assert worker_count() == DEFAULT_WORKERS
        assert worker_count({"independent_workers": 3}) == 3
        monkeypatch.setenv("JEPSEN_TPU_WORKERS", "5")
        assert worker_count() == 5
        assert worker_count({"independent_workers": 3}) == 3
        assert worker_count({"independent_workers": 3}, explicit=2) == 2

    def test_independent_host_order_deterministic(self):
        h = keyed_history(n_keys=4, n_ops=20, seed=25)
        checker = IndependentChecker(Stats(), max_workers=4)
        res = checker.check({"name": "t"}, h, {})
        assert list(res["results"]) == history_keys(h)

    def test_compilation_cache_cpu_gated(self, monkeypatch):
        from jepsen_tpu.ops.cache import init_compilation_cache
        monkeypatch.delenv("JEPSEN_TPU_CACHE_CPU", raising=False)
        # CPU backend without the override: stays off, never raises
        assert init_compilation_cache() == ""

    @pytest.fixture
    def cache_config(self, monkeypatch):
        """The persistent cache armed on the CPU backend, with JAX's
        process-global cache settings put back afterwards."""
        import jax
        monkeypatch.setenv("JEPSEN_TPU_CACHE_CPU", "1")
        keys = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs")
        before = {k: getattr(jax.config, k) for k in keys}
        yield jax.config
        for k, v in before.items():
            jax.config.update(k, v)

    def test_compilation_cache_fixed_dir_any_cwd(self, tmp_path,
                                                 monkeypatch, cache_config):
        import os
        import jepsen_tpu
        from jepsen_tpu.ops import cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.chdir(tmp_path)
        checkout = os.path.dirname(os.path.dirname(
            os.path.abspath(jepsen_tpu.__file__)))
        d = cache.init_compilation_cache()
        # one fixed in-checkout path: not cwd, not any store argument
        assert d == os.path.join(checkout, "store", "cache", "xla")
        assert cache_config.jax_compilation_cache_dir == d
        assert os.path.isdir(d)

    def test_compilation_cache_env_dir_left_alone(self, tmp_path,
                                                  monkeypatch, cache_config):
        from jepsen_tpu.ops.cache import init_compilation_cache
        # JAX reads the variable itself at import; with it set the
        # program writes no directory of its own over JAX's
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = cache_config.jax_compilation_cache_dir
        init_compilation_cache()
        assert cache_config.jax_compilation_cache_dir == before
