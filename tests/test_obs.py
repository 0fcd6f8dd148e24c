"""The telescope (jepsen_tpu.obs): tracing, histograms, flight recorder.

Covers the trace-context primitives (id minting, tolerant wire parsing,
the per-request wall anchor), the pow2-ladder histograms (observe /
percentile / cross-process merge), the bounded flight recorder (off-path
no-op, ring bound, Chrome export), the compile-timing wrapper, the
``Request`` causal-tree assembly (context propagation, absorb dedup,
orphan-free merges), the service/fleet integration (lifecycle-edge
histograms, ``merged_trace``, the fleet-wide scrape), and the web
``/trace`` endpoint.  Wire propagation across a REAL process boundary
(>= 2 pids in one merged trace) runs under the ``slow`` marker.
"""

import json
import os
import threading
import urllib.error
import urllib.request

import pytest

from jepsen_tpu.obs.hist import (
    Histogram, HistogramSet, merge_hist_snapshots, timed_first_call,
)
from jepsen_tpu.obs.recorder import FlightRecorder
from jepsen_tpu.obs.trace import (
    CTX_PARENT, CTX_TRACE, chrome_document, chrome_events_from_trace,
    make_context, new_span_id, new_trace_id, parse_context,
)
from jepsen_tpu.serve import CheckService
from jepsen_tpu.serve.request import KIND_WGL, Request
from jepsen_tpu.synth import cas_register_history


def audit(trace):
    """(orphans, pids) of a merged trace payload: an orphan is a remote
    whose parent-span-id names no span in the tree."""
    ids = {trace["span-id"]} | {r["span-id"] for r in trace["remote"]}
    orphans = [r for r in trace["remote"]
               if r["parent-span-id"] not in ids]
    pids = {trace["pid"]} | {r["pid"] for r in trace["remote"]}
    return orphans, pids


class TestTraceContext:
    def test_id_shapes(self):
        tids = {new_trace_id() for _ in range(64)}
        sids = {new_span_id() for _ in range(64)}
        assert len(tids) == 64 and len(sids) == 64
        assert all(len(t) == 16 and int(t, 16) >= 0 for t in tids)
        assert all(len(s) == 8 and int(s, 16) >= 0 for s in sids)

    def test_context_round_trip(self):
        ctx = make_context("ab" * 8, "cd" * 4)
        parsed = parse_context(ctx)
        assert parsed[CTX_TRACE] == "ab" * 8
        assert parsed[CTX_PARENT] == "cd" * 4

    def test_parse_tolerates_garbage(self):
        for bad in (None, 42, "x", [], {}, {CTX_TRACE: 7, CTX_PARENT: ""}):
            parsed = parse_context(bad)
            assert parsed == {CTX_TRACE: None, CTX_PARENT: None}

    def test_request_mints_root(self):
        r = Request(cas_register_history(10, seed=0), KIND_WGL, {})
        assert len(r.trace_id) == 16 and len(r.span_id) == 8
        assert r.parent_span_id is None
        assert r.anchor_unix_s > 1e9      # a plausible unix wall reading

    def test_request_adopts_context(self):
        parent = Request(cas_register_history(10, seed=0), KIND_WGL, {})
        child = Request(cas_register_history(10, seed=1), KIND_WGL, {},
                        trace=parent.trace_context())
        assert child.trace_id == parent.trace_id
        assert child.parent_span_id == parent.span_id
        assert child.span_id != parent.span_id

    def test_absorb_builds_tree_and_dedupes(self):
        root = Request(cas_register_history(10, seed=0), KIND_WGL, {})
        child = Request(cas_register_history(10, seed=1), KIND_WGL, {},
                        trace=root.trace_context())
        child.span("verdict")
        result = {"valid": True, "serve": child.trace_payload()}
        root.absorb_serve(result)
        root.absorb_serve(result)        # finish() re-absorbs; must dedupe
        payload = root.trace_payload()
        assert len(payload["remote"]) == 1
        assert payload["remote"][0]["span-id"] == child.span_id
        assert payload["remote"][0]["parent-span-id"] == root.span_id
        assert audit(payload) == ([], {os.getpid()})

    def test_absorb_drops_foreign_trace(self):
        root = Request(cas_register_history(10, seed=0), KIND_WGL, {})
        stranger = Request(cas_register_history(10, seed=1), KIND_WGL, {})
        root.absorb_serve({"serve": stranger.trace_payload()})
        assert root.trace_payload()["remote"] == []

    def test_chrome_events_from_trace(self):
        root = Request(cas_register_history(10, seed=0), KIND_WGL, {})
        root.span("pack")
        root.span("dispatch")
        root.span("verdict")
        events = chrome_events_from_trace(root.trace_payload())
        assert [e["name"] for e in events] == [
            "enqueue->pack", "pack->dispatch", "dispatch->verdict"]
        for e in events:
            assert e["ph"] == "X" and e["dur"] >= 1.0
            assert e["pid"] == os.getpid() and e["tid"] == root.id
            assert e["args"]["trace-id"] == root.trace_id
        doc = chrome_document(events)
        assert doc["displayTimeUnit"] == "ms"
        json.loads(json.dumps(doc))      # plain-JSON round trip


class TestHistograms:
    def test_pow2_bucketing_and_percentiles(self):
        h = Histogram()
        for us in (1, 3, 100, 1000, 1000):
            h.observe(us / 1e6)
        assert h.count == 5
        # 3 µs lands in the 4 µs bucket, 100 µs in 128, 1000 µs in 1024
        assert set(h.buckets) == {1, 4, 128, 1024}
        assert h.percentile(99) == pytest.approx(1024 / 1e6)
        assert h.percentile(50) == pytest.approx(128 / 1e6)
        snap = h.snapshot()
        assert snap["count"] == 5 and snap["buckets-us"]["1024"] == 2
        assert snap["p99"] >= snap["p90"] >= snap["p50"] > 0

    def test_empty_percentile_is_zero(self):
        assert Histogram().percentile(99) == 0.0

    def test_merge_is_bucket_wise_addition(self):
        sets = [HistogramSet(), HistogramSet()]
        for i, hs in enumerate(sets):
            for _ in range(10):
                hs.observe("edge:a->b", 0.001 * (i + 1))
        merged = merge_hist_snapshots(
            [hs.snapshot() for hs in sets] + [None, {"junk": 3}])
        assert merged["edge:a->b"]["count"] == 20
        assert sum(
            merged["edge:a->b"]["buckets-us"].values()) == 20
        # malformed worker snapshots are skipped, not fatal
        assert "junk" not in merged

    def test_merge_skips_are_counted(self):
        """Silent drops are the availability call; *silent* silent drops
        are not — every malformed per-histogram entry bumps the
        process-wide counter that Metrics.snapshot() surfaces as
        ``hist-merge-skipped``.  A whole-snapshot None (the worker-
        unreachable convention) is protocol, not corruption, and must
        NOT count."""
        from jepsen_tpu.obs.hist import merge_skipped_count
        before = merge_skipped_count()
        hs = HistogramSet()
        hs.observe("edge:a->b", 0.001)
        merge_hist_snapshots([hs.snapshot(), None])   # protocol: free
        assert merge_skipped_count() == before
        merge_hist_snapshots([
            {"junk": 3},                              # non-dict entry
            {"bad": {"buckets-us": {"x": "y"}}},      # uncastable buckets
            hs.snapshot()])
        assert merge_skipped_count() == before + 2

    def test_concurrent_observe(self):
        hs = HistogramSet()

        def hammer(k):
            for i in range(200):
                hs.observe(f"h{k % 2}", 0.0001 * (i + 1))

        threads = [threading.Thread(target=hammer, args=(k,))
                   for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        snap = hs.snapshot()
        assert snap["h0"]["count"] == 400 and snap["h1"]["count"] == 400

    def test_timed_first_call_observes_once(self):
        calls = []
        fn = timed_first_call(lambda x: calls.append(x) or x * 2,
                              "compile:test:w8")
        assert fn(3) == 6 and fn(4) == 8 and fn(5) == 10
        assert calls == [3, 4, 5]
        from jepsen_tpu.obs.hist import compile_hist_stats
        snap = compile_hist_stats()
        assert snap["compile:test:w8"]["count"] == 1


class TestFlightRecorder:
    def test_disabled_records_nothing(self):
        rec = FlightRecorder(capacity=8, enabled=False)
        rec.record("dispatch", "x", dur_s=0.1)
        assert rec.stats() == {"enabled": False, "capacity": 8,
                               "recorded": 0, "buffered": 0, "dropped": 0}

    def test_ring_bound_and_drop_accounting(self):
        rec = FlightRecorder(capacity=4, enabled=True)
        for i in range(10):
            rec.record("retry", f"e{i}")
        s = rec.stats()
        assert s["recorded"] == 10 and s["buffered"] == 4
        assert s["dropped"] == 6
        assert [e["name"] for e in rec.snapshot()] == [
            "e6", "e7", "e8", "e9"]

    def test_env_knobs(self, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_FLIGHT_RECORDER", "1")
        monkeypatch.setenv("JEPSEN_TPU_FLIGHT_EVENTS", "17")
        rec = FlightRecorder()
        assert rec.enabled and rec.capacity == 17
        monkeypatch.setenv("JEPSEN_TPU_FLIGHT_RECORDER", "0")
        assert not FlightRecorder().enabled

    def test_chrome_events_and_export(self, tmp_path):
        rec = FlightRecorder(capacity=8, enabled=True)
        rec.record("dispatch", "batch:wgl:x3", dur_s=0.002,
                   trace_id="t" * 16, span_id="s" * 8, args={"lanes": 3})
        rec.record("chaos", "inject:fleet:kill:0")
        evs = rec.chrome_events()
        assert evs[0]["ph"] == "X" and evs[0]["dur"] == pytest.approx(2000)
        assert evs[0]["args"]["trace-id"] == "t" * 16
        assert evs[1]["ph"] == "i" and evs[1]["s"] == "t"
        path = rec.export_chrome(str(tmp_path / "flight.json"))
        with open(path) as f:
            doc = json.load(f)
        assert len(doc["traceEvents"]) == 2

    def test_clear(self):
        rec = FlightRecorder(capacity=8, enabled=True)
        rec.record("retry", "x")
        rec.clear()
        assert rec.stats()["recorded"] == 0 and rec.snapshot() == []


class TestServiceIntegration:
    @pytest.fixture(scope="class")
    def svc(self):
        with CheckService(max_lanes=8) as s:
            yield s

    def test_edges_and_merged_trace(self, svc):
        req = svc.submit(cas_register_history(30, seed=3), kind="wgl",
                         model="cas-register")
        res = req.wait(timeout=120)
        serve = res["serve"]
        for k in ("request-id", "trace-id", "span-id", "parent-span-id",
                  "anchor-unix-s", "pid", "spans", "remote"):
            assert k in serve, f"serve payload missing {k}"
        assert serve["parent-span-id"] is None
        assert serve["pid"] == os.getpid()
        snap = svc.metrics.snapshot()
        for edge in ("edge:enqueue->dispatch", "edge:dispatch->verdict"):
            h = snap["histograms"][edge]
            assert h["count"] >= 1 and h["p99"] >= h["p50"] > 0
        merged = svc.merged_trace(req.id)
        assert merged is not None
        assert merged["trace-id"] == serve["trace-id"]
        assert svc.merged_trace("no-such-request") is None

    def test_submitted_context_adopted(self, svc):
        ctx = make_context("f" * 16, "0" * 8)
        req = svc.submit(cas_register_history(20, seed=4), kind="wgl",
                         model="cas-register", trace=ctx)
        res = req.wait(timeout=120)
        assert res["serve"]["trace-id"] == "f" * 16
        assert res["serve"]["parent-span-id"] == "0" * 8

    def test_compile_histogram_keyed_by_cache_bucket(self, svc):
        svc.submit(cas_register_history(20, seed=5), kind="wgl",
                   model="cas-register").wait(timeout=120)
        snap = svc.metrics.snapshot()
        compiles = [k for k in snap["histograms"]
                    if k.startswith("compile:")]
        assert compiles, "no compile histogram after a first dispatch"
        assert all(snap["histograms"][k]["p50"] > 0 for k in compiles)


class TestProcFleetTracing:
    def test_wire_trace_fully_connected(self):
        from jepsen_tpu.serve.fleet import ProcFleet
        # hedge_s: no hedge.  This test counts hops, and a first compile
        # slower than the default hedge (2 s) sent the request to the
        # sibling too: four hops on a busy machine (ROADMAP D11).
        fleet = ProcFleet(workers=2, spawn=False, max_lanes=8,
                          capacity=64, default_deadline_s=60.0,
                          hedge_s=60.0)
        try:
            req = fleet.submit(cas_register_history(30, seed=6),
                               kind="wgl", model="cas-register")
            req.wait(timeout=120)
            trace = fleet.merged_trace(req.id)
            assert trace is not None
            # root -> wire client -> worker request: two absorbed hops
            assert len(trace["remote"]) == 2
            orphans, _ = audit(trace)
            assert orphans == []
            parents = {r["parent-span-id"] for r in trace["remote"]}
            assert trace["span-id"] in parents
            snaps = fleet.worker_snapshots()
            assert len(snaps) == 2 and all(s is not None for s in snaps)
            snap = fleet.metrics.snapshot()
            assert [w["worker"] for w in snap["workers"]] == [0, 1]
            assert any(k.startswith("edge:")
                       for k in snap["histograms"])
        finally:
            fleet.close(timeout=30.0)

    @pytest.mark.slow
    def test_spawned_trace_spans_two_pids(self):
        from jepsen_tpu.serve.fleet import ProcFleet
        fleet = ProcFleet(workers=2, spawn=True, max_lanes=8,
                          capacity=64, default_deadline_s=60.0)
        try:
            req = fleet.submit(cas_register_history(30, seed=7),
                               kind="wgl", model="cas-register")
            req.wait(timeout=180)
            trace = fleet.merged_trace(req.id)
            orphans, pids = audit(trace)
            assert orphans == []
            assert len(pids) >= 2, (
                f"one pid in a cross-process trace: {pids}")
            assert os.getpid() in pids
        finally:
            fleet.close(timeout=30.0)


class TestWebTrace:
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

    def test_trace_endpoint(self, server):
        url, svc = server
        res = svc.check(cas_register_history(30, seed=8), kind="wgl",
                        model="cas-register")
        rid = res["serve"]["request-id"]
        trace = json.loads(
            urllib.request.urlopen(f"{url}/trace/{rid}").read())
        assert trace["request-id"] == rid
        assert trace["trace-id"] == res["serve"]["trace-id"]
        doc = json.loads(urllib.request.urlopen(
            f"{url}/trace/{rid}?perfetto=1").read())
        assert doc["traceEvents"], "perfetto view exported no events"
        assert all(e["ph"] in ("X", "i") for e in doc["traceEvents"])

    def test_trace_unknown_404(self, server):
        url, _ = server
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{url}/trace/99999")
        assert ei.value.code == 404
