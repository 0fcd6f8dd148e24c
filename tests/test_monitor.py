"""Online monitoring (jepsen_tpu.monitor): tap, incremental frontiers,
early refutation, and the resumed final check.

The load-bearing assertions are the parity fuzz: the incremental
KeyFrontier must produce *exactly* the cold wgl_cpu verdict (validity,
refuting op, configs-explored) for the same history regardless of how
the stream is chunked across epochs — that identity is what lets
core.analyze resume the authoritative check from monitor state instead
of re-checking from op 0.  Satellite coverage: the derived wgl start
capacity + env override, scheduler aging (aged_picks), and the shared
monotonic clock.
"""

import json
import os
import threading

import pytest

from jepsen_tpu import client as jclient
from jepsen_tpu import core
from jepsen_tpu import generator as gen
from jepsen_tpu.checker import Stats, compose, wgl_cpu
from jepsen_tpu.checker.linearizable import Linearizable, linearizable
from jepsen_tpu.engine import ladder
from jepsen_tpu.history import History, INVOKE, NEMESIS, Op
from jepsen_tpu.independent import IndependentChecker, subhistory
from jepsen_tpu.models import CASRegister
from jepsen_tpu.monitor import DEFAULT_EPOCH_OPS, Monitor, active_statuses
from jepsen_tpu.monitor import resume as mon_resume
from jepsen_tpu.monitor.epochs import (
    ElleEpochEngine, KeyFrontier, WglEpochEngine,
)
from jepsen_tpu.monitor.tap import OpTap
from jepsen_tpu.serve.metrics import Metrics, mono_now
from jepsen_tpu.synth import (
    cas_register_history, corrupt_list_append, corrupt_reads,
    list_append_history,
)
from tests.test_core_store import base_test
from tests.test_interpreter import MockRegisterClient, rwc_gen
from tests.test_serve import keyed_history


def _ops(n=4):
    return [Op(process=0, type=INVOKE, f="read", value=None, index=i)
            for i in range(n)]


class TestOpTap:
    def test_offer_drain_order(self):
        tap = OpTap(16)
        ops = _ops(5)
        for op in ops:
            assert tap.offer(op) is True
        assert tap.drain() == ops
        assert tap.drain() == []
        assert tap.offered == 5 and tap.dropped == 0

    def test_full_tap_drops_newest_and_counts(self):
        tap = OpTap(3)
        ops = _ops(5)
        results = [tap.offer(op) for op in ops]
        assert results == [True, True, True, False, False]
        assert tap.dropped == 2 and tap.offered == 5
        # the oldest ops are the ones kept: the frontier needs contiguity
        # from the front, so the tail is what gets sacrificed
        assert tap.drain() == ops[:3]

    def test_wake_fires_at_backlog(self):
        tap = OpTap(64)
        ev = threading.Event()
        tap.bind_wake(ev, 3)
        for op in _ops(2):
            tap.offer(op)
        assert not ev.is_set()
        tap.offer(_ops(3)[2])
        assert ev.is_set()

    def test_stats_shape(self):
        tap = OpTap(8)
        tap.offer(_ops(1)[0])
        s = tap.stats()
        assert s == {"offered": 1, "dropped": 0, "backlog": 1,
                     "capacity": 8}


def _feed_chunked(frontier, history, chunk):
    ops = list(history)
    for i in range(0, len(ops), chunk):
        for op in ops[i:i + chunk]:
            frontier.feed(op)
        frontier.advance()
    frontier.finalize()


class TestKeyFrontierParity:
    """The frontier IS wgl_cpu's search, fed incrementally: identical
    verdicts and identical configs-explored, for every chunking."""

    @pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
    def test_clean_history_parity(self, seed):
        h = cas_register_history(200, concurrency=4, seed=seed)
        cold = wgl_cpu.check(CASRegister(), h)
        assert cold["valid"] is True
        f = KeyFrontier(CASRegister())
        _feed_chunked(f, h, chunk=37)
        v = f.verdict()
        assert v["valid"] is True
        assert v["configs-explored"] == cold["configs-explored"]

    def test_chunking_is_irrelevant(self):
        h = cas_register_history(150, concurrency=4, seed=11)
        verdicts = []
        for chunk in (1, 7, len(h)):
            f = KeyFrontier(CASRegister())
            _feed_chunked(f, h, chunk)
            verdicts.append(f.verdict())
        assert verdicts[0] == verdicts[1] == verdicts[2]

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_corrupted_history_refutes_like_cold(self, seed):
        h = corrupt_reads(cas_register_history(300, concurrency=4,
                                               seed=seed),
                          n=1, seed=seed)
        cold = wgl_cpu.check(CASRegister(), h)
        assert cold["valid"] is False
        f = KeyFrontier(CASRegister())
        _feed_chunked(f, h, chunk=53)
        assert f.result is not None
        assert f.result["valid"] is False
        assert f.result["op"] == cold["op"]      # same refuting op
        assert isinstance(f.result["op-index"], int)

    def test_refutation_is_sticky_and_stream_discarded(self):
        h = corrupt_reads(cas_register_history(200, seed=5), n=1, seed=5)
        f = KeyFrontier(CASRegister())
        _feed_chunked(f, h, chunk=31)
        r1 = dict(f.result)
        # more ops after a refutation change nothing
        for op in cas_register_history(40, seed=6):
            f.feed(op)
        f.advance()
        assert f.result == r1

    def test_horizon_buffers_open_invokes(self):
        f = KeyFrontier(CASRegister())
        f.feed(Op(process=0, type=INVOKE, f="write", value=1, index=0))
        f.advance()
        # completion class unknown: nothing entered yet
        assert f.ops_entered == 0 and f.pending_ops() == 1
        f.feed(Op(process=0, type="ok", f="write", value=1, index=1))
        f.advance()
        assert f.ops_entered == 1 and f.ops_checked == 1

    def test_explosion_degrades_to_unknown_not_false(self):
        h = cas_register_history(120, concurrency=5, seed=9)
        f = KeyFrontier(CASRegister(), max_configs=1)
        _feed_chunked(f, h, chunk=17)
        v = f.verdict()
        assert v["valid"] == "unknown"
        assert "error" in v


class TestWglEpochEngine:
    def test_independent_routing_matches_subhistory(self):
        h = keyed_history(n_keys=3, n_ops=40, seed=2)
        eng = WglEpochEngine(CASRegister(), independent=True)
        eng.feed(list(h))
        eng.advance()
        eng.finalize()
        assert sorted(eng.frontiers) == [0, 1, 2]
        for k in eng.frontiers:
            cold = wgl_cpu.check(CASRegister(), subhistory(k, h))
            v = eng.frontiers[k].verdict()
            assert v["valid"] is cold["valid"] is True
            assert v["configs-explored"] == cold["configs-explored"]

    def test_independent_matches_independent_checker(self):
        h = keyed_history(n_keys=2, n_ops=30, seed=4)
        cold = IndependentChecker(
            Linearizable(CASRegister(), algorithm="cpu")).check({}, h)
        eng = WglEpochEngine(CASRegister(), independent=True)
        eng.feed(list(h))
        eng.finalize()
        per_key = {k: f.verdict() for k, f in eng.frontiers.items()}
        assert cold["valid"] is True
        assert {k: v["valid"] for k, v in per_key.items()} \
            == {k: r["valid"] for k, r in cold["results"].items()}

    def test_nemesis_and_unkeyed_ops_dropped(self):
        eng = WglEpochEngine(CASRegister(), independent=True)
        eng.feed([Op(process=NEMESIS, type="info", f="start", value=None),
                  Op(process=0, type=INVOKE, f="read", value=None)])
        assert eng.frontiers == {}

    def test_counters_shape(self):
        eng = WglEpochEngine(CASRegister())
        eng.feed(list(cas_register_history(30, seed=1)))
        eng.advance()
        c = eng.counters()
        assert set(c) == {"keys", "ops-entered", "ops-checked",
                          "configs-explored", "pending-ops"}
        assert c["keys"] == 1 and c["ops-checked"] > 0


class TestMonitorResume:
    """resume_final_check returns the cold offline verdict from frontier
    state — or None whenever soundness is in any doubt."""

    def _monitored(self, h, **kw):
        m = Monitor(kind="wgl", model=CASRegister(), **kw)
        for op in h:
            m.offer(op)
        return m

    def test_clean_resume_matches_cold_analyze(self, tmp_path):
        h = cas_register_history(300, concurrency=4, seed=3)
        cold = wgl_cpu.check(CASRegister(), h)
        m = self._monitored(h, store_dir=str(tmp_path))
        m.flush()
        checker = Linearizable(CASRegister(), algorithm="cpu")
        res = mon_resume.resume_final_check({}, checker, h, m)
        assert res is not None
        assert res["analyzer"] == "monitor-resume"
        assert res["valid"] is cold["valid"] is True
        assert res["configs-explored"] == cold["configs-explored"]

    def test_tail_accounting(self):
        h = list(cas_register_history(400, concurrency=4, seed=8))
        m = Monitor(kind="wgl", model=CASRegister())
        for op in h[:300]:
            m.offer(op)
        m.flush()                      # epoch 1 pays for the first 300
        mid_checked = m.engine.counters()["ops-checked"]
        for op in h[300:]:
            m.offer(op)
        checker = Linearizable(CASRegister(), algorithm="cpu")
        res = mon_resume.resume_final_check({}, checker, History(h), m)
        assert res["valid"] is True
        assert res["tail-ops"] == len(h) - 300
        assert res["resumed-from-epoch"] == 1
        # the resumed check re-checked only the tail, not the run
        total_checked = m.engine.counters()["ops-checked"]
        assert res["ops-rechecked"] == total_checked - mid_checked
        assert 0 < res["ops-rechecked"] < total_checked

    def test_refuted_resume_carries_op_index(self):
        h = corrupt_reads(cas_register_history(300, seed=7), n=1, seed=7,
                          within=0.4)
        m = self._monitored(h)
        checker = Linearizable(CASRegister(), algorithm="cpu")
        res = mon_resume.resume_final_check({}, checker, History(list(h)),
                                            m)
        assert res["valid"] is False
        assert isinstance(res["op-index"], int)
        cold = wgl_cpu.check(CASRegister(), h)
        assert cold["valid"] is False and res["op"] == cold["op"]

    def test_independent_resume_shape(self):
        h = keyed_history(n_keys=2, n_ops=30, seed=6)
        m = Monitor(kind="wgl", model=CASRegister(), independent=True)
        for op in h:
            m.offer(op)
        checker = IndependentChecker(
            Linearizable(CASRegister(), algorithm="cpu"))
        res = mon_resume.resume_final_check({}, checker, h, m)
        assert res["valid"] is True
        assert res["key-count"] == 2
        assert res["failures"] == []
        assert set(res["results"]) == {0, 1}

    def test_poisoned_tap_falls_back_cold(self):
        h = cas_register_history(100, seed=2)
        m = Monitor(kind="wgl", model=CASRegister(), tap_capacity=8)
        for op in h:
            m.offer(op)
        assert m.poisoned is not None
        checker = Linearizable(CASRegister(), algorithm="cpu")
        assert mon_resume.resume_final_check({}, checker, h, m) is None

    def test_checker_mismatch_falls_back_cold(self):
        h = cas_register_history(60, seed=2)
        m = self._monitored(h)
        # independent-mode mismatch
        ic = IndependentChecker(Linearizable(CASRegister(),
                                             algorithm="cpu"))
        assert mon_resume.resume_final_check({}, ic, h, m) is None
        # a compose with no monitorable child, or whose monitorable child
        # mismatches the monitor's mode, goes cold as a whole
        assert mon_resume.resume_final_check(
            {}, compose({"stats": Stats()}), h, m) is None
        assert mon_resume.resume_final_check(
            {}, compose({"stats": Stats(), "workload": ic}), h, m) is None

    def test_compose_resumes_monitored_child(self):
        h = cas_register_history(60, seed=2)
        m = self._monitored(h)
        c = compose({"stats": Stats(),
                     "workload": linearizable(CASRegister(),
                                              algorithm="cpu")})
        res = mon_resume.resume_final_check({"name": "t"}, c, h, m)
        assert res is not None
        assert res["analyzer"] == "monitor-resume"
        assert res["monitored-child"] == "workload"
        assert res["workload"]["analyzer"] == "monitor-resume"
        cold = wgl_cpu.check(CASRegister(), h)
        assert res["workload"]["valid"] is cold["valid"]
        assert res["workload"]["configs-explored"] == \
            cold["configs-explored"]
        # the sibling ran its normal cold check and merged in
        assert "count" in res["stats"]
        from jepsen_tpu.checker.core import merge_valid
        assert res["valid"] == merge_valid([res["stats"]["valid"],
                                            res["workload"]["valid"]])

    def test_nested_compose_resumes(self):
        h = cas_register_history(40, seed=5)
        m = self._monitored(h)
        inner = compose({"workload": linearizable(CASRegister(),
                                                  algorithm="cpu")})
        c = compose({"stats": Stats(), "inner": inner})
        res = mon_resume.resume_final_check({"name": "t"}, c, h, m)
        assert res is not None
        assert res["monitored-child"] == "inner"
        assert res["inner"]["workload"]["analyzer"] == "monitor-resume"

    def test_op_count_mismatch_falls_back_cold(self):
        h = list(cas_register_history(80, seed=3))
        m = self._monitored(h[:-5])   # tap missed the last 5 ops
        checker = Linearizable(CASRegister(), algorithm="cpu")
        assert mon_resume.resume_final_check({}, checker, History(h),
                                             m) is None

    def test_elle_monitor_never_resumes(self):
        m = Monitor(kind="elle")
        checker = Linearizable(CASRegister(), algorithm="cpu")
        assert mon_resume.resume_final_check({}, checker, History([]),
                                             m) is None

    def test_empty_history_vacuously_valid(self):
        m = Monitor(kind="wgl", model=CASRegister())
        checker = Linearizable(CASRegister(), algorithm="cpu")
        res = mon_resume.resume_final_check({}, checker, History([]), m)
        assert res["valid"] is True

    def test_checkpoint_roundtrip(self, tmp_path):
        h = cas_register_history(100, seed=4)
        m = self._monitored(h, store_dir=str(tmp_path))
        m.flush()
        m.finalize()
        path = os.path.join(str(tmp_path), mon_resume.CHECKPOINT)
        assert os.path.exists(path)
        rec = mon_resume.load(str(tmp_path))
        assert rec["version"] == mon_resume.VERSION
        assert rec["kind"] == "wgl" and rec["finalized"] is True
        assert rec["tap"]["offered"] == len(h)
        assert rec["keys"]["None"]["valid"] is True
        assert mon_resume.load(str(tmp_path / "nope")) is None


class TestMonitorLifecycle:
    def test_early_refutation_and_abort_signal(self, tmp_path):
        h = corrupt_reads(cas_register_history(600, seed=7), n=1, seed=1,
                          within=0.3)
        m = Monitor(kind="wgl", model=CASRegister(), abort=True,
                    epoch_ops=64, store_dir=str(tmp_path))
        refuted_at = None
        for i, op in enumerate(h):
            m.offer(op)
            if (i + 1) % 64 == 0:
                m.flush()
            if m.should_abort():
                refuted_at = i
                break
        assert refuted_at is not None and refuted_at < len(h) - 1, \
            "the refutation must land before the stream ends"
        st = m.channel.status()
        assert st["refuted"] is True and st["abort-enabled"] is True
        assert isinstance(st["verdict"]["op-index"], int)
        # the refuting op is inside what the monitor consumed
        assert st["verdict"]["op-index"] <= refuted_at
        # snapshot artifact was written atomically
        snap = json.load(open(tmp_path / "monitor-refutation.json"))
        assert snap["confirmed"] is True
        assert snap["result"]["valid"] is False

    def test_unrefuted_monitor_never_aborts(self):
        m = Monitor(kind="wgl", model=CASRegister(), abort=True)
        for op in cas_register_history(100, seed=1):
            m.offer(op)
        m.flush()
        assert m.should_abort() is False

    def test_flusher_thread_and_registry(self):
        m = Monitor(kind="wgl", model=CASRegister(), epoch_ops=16,
                    epoch_s=0.05)
        m.start()
        try:
            assert any(s["id"] == m.id and s["active"]
                       for s in active_statuses())
            for op in cas_register_history(120, seed=5):
                m.offer(op)
            deadline = mono_now() + 5.0
            while not m.epochs and mono_now() < deadline:
                pass
            assert m.epochs, "flusher thread never produced an epoch"
        finally:
            m.finalize()
        assert m.finalized
        # finalize deregisters but keeps the final status visible
        assert any(s["id"] == m.id and not s["active"]
                   for s in active_statuses())
        m.close()  # idempotent

    def test_epoch_records_have_counters(self):
        m = Monitor(kind="wgl", model=CASRegister())
        for op in cas_register_history(80, seed=6):
            m.offer(op)
        rec = m.flush()
        assert rec["epoch"] == 1 and rec["new-ops"] > 0
        assert rec["ops-checked"] > 0 and "t" in rec
        assert m.flush() is None     # nothing new: no empty epochs

    def test_status_shape(self):
        m = Monitor(kind="wgl", model=CASRegister(), name="t")
        s = m.status()
        assert s["kind"] == "wgl" and s["name"] == "t"
        assert s["poisoned"] is None and s["epochs"] == 0
        assert s["verdict"]["refuted"] is False


class TestMonitorFromTest:
    def test_disabled_without_flag(self):
        assert Monitor.from_test({"checker": linearizable(
            CASRegister(), algorithm="cpu")}) is None

    def test_bare_linearizable(self):
        m = Monitor.from_test({"monitor": True, "checker": linearizable(
            CASRegister(), algorithm="cpu")})
        assert m is not None and m.kind == "wgl" and not m.independent

    def test_compose_picks_monitorable_child(self):
        m = Monitor.from_test({"monitor": True, "checker": compose({
            "stats": Stats(),
            "linear": linearizable(CASRegister(), algorithm="cpu")})})
        assert m is not None and m.kind == "wgl"

    def test_independent_checker(self):
        m = Monitor.from_test({"monitor": True,
                               "checker": IndependentChecker(
                                   Linearizable(CASRegister(),
                                                algorithm="cpu"))})
        assert m is not None and m.independent is True

    def test_unmonitorable_checker_yields_none(self):
        assert Monitor.from_test({"monitor": True,
                                  "checker": Stats()}) is None

    def test_opts_honored(self):
        m = Monitor.from_test({"monitor": True, "monitor_epoch": 32,
                               "monitor_abort": True,
                               "checker": linearizable(
                                   CASRegister(), algorithm="cpu")})
        assert m.epoch_ops == 32
        assert m.channel.abort_enabled is True
        m2 = Monitor.from_test({"monitor": True, "checker": linearizable(
            CASRegister(), algorithm="cpu")})
        assert m2.epoch_ops == DEFAULT_EPOCH_OPS


class TestElleEpochEngine:
    """Elle epochs check the accumulated prefix as a run-ended-here
    history; a corrupted stream is flagged before it ends."""

    def test_clean_prefixes_stay_valid(self):
        eng = ElleEpochEngine(workload="list-append")
        h = list(list_append_history(n_txns=40, seed=3))
        eng.feed(h[:len(h) // 2])
        assert eng.advance() is None
        eng.feed(h[len(h) // 2:])
        assert eng.advance() is None
        assert eng.last["valid"] is True
        assert eng.counters()["ops-ingested"] == len(h)

    def test_corrupted_stream_refutes_before_end(self):
        h = list(corrupt_list_append(
            list_append_history(n_txns=80, seed=5),
            anomaly_p=0.4, seed=5))
        eng = ElleEpochEngine(workload="list-append")
        refuted_at = None
        chunk = 40
        for i in range(0, len(h), chunk):
            eng.feed(h[i:i + chunk])
            if eng.advance() is not None:
                refuted_at = i + chunk
                break
        assert refuted_at is not None and refuted_at < len(h)
        assert eng.result["valid"] is False
        assert isinstance(eng.result["op-index"], int)

    def test_open_invokes_become_info_cut(self):
        eng = ElleEpochEngine(workload="list-append")
        eng.feed([Op(process=0, type=INVOKE, f="txn",
                     value=[["append", 0, 1]])])
        pfx = eng._prefix()
        assert len(pfx) == 2
        assert pfx[1].type == "info" and pfx[1].error == ":monitor-cut"
        # the cut txn carries WHICH epoch cut it as a trailing
        # ["monitor-cut", None, epoch] micro-op (1-based, pre-advance)
        assert pfx[1].value == [["append", 0, 1],
                                ["monitor-cut", None, 1]]
        eng.advance()
        assert eng._prefix()[1].value[-1] == ["monitor-cut", None, 2]


class TestMonitoredRun:
    """End-to-end core.run with --monitor: the whole loop from the
    interpreter tap through the resumed authoritative check."""

    def test_clean_run_resumes_and_matches_cold(self, tmp_path):
        t = core.run(base_test(
            tmp_path,
            client=MockRegisterClient(),
            generator=gen.clients(rwc_gen(80)),
            checker=linearizable(CASRegister(), algorithm="cpu"),
            monitor=True, monitor_epoch=16))
        res = t["results"]
        assert res["valid"] is True
        assert res["analyzer"] == "monitor-resume"
        cold = wgl_cpu.check(CASRegister(), t["history"])
        assert cold["valid"] is True
        assert res["configs-explored"] == cold["configs-explored"]
        # checkpoint artifact landed in the store
        assert os.path.exists(os.path.join(t["store_dir"],
                                           "monitor.json"))

    def test_buggy_run_aborts_early_with_refuting_op(self, tmp_path):
        n = 600
        t = core.run(base_test(
            tmp_path,
            client=MockRegisterClient(stale=True),
            generator=gen.clients(rwc_gen(n)),
            checker=linearizable(CASRegister(), algorithm="cpu"),
            monitor=True, monitor_epoch=8, monitor_abort=True))
        assert t["results"]["valid"] is False
        assert t.get("monitor_aborted") is True
        invokes = sum(1 for o in t["history"]
                      if o.type == INVOKE and o.process != NEMESIS)
        assert invokes < n, "the generator must be cut before exhaustion"
        assert os.path.exists(os.path.join(t["store_dir"],
                                           "monitor-refutation.json"))

    def test_unmonitored_run_unaffected(self, tmp_path):
        t = core.run(base_test(
            tmp_path,
            client=MockRegisterClient(),
            generator=gen.clients(rwc_gen(40)),
            checker=linearizable(CASRegister(), algorithm="cpu")))
        assert t["results"]["valid"] is True
        assert t["results"].get("analyzer") != "monitor-resume"


class TestServeSatellites:
    def test_wgl_start_capacity_preserves_old_default(self):
        # w=8 (the common small-history bucket) derives the old fixed 256
        assert ladder.wgl_start_capacity(64, 8) == 256
        assert ladder.wgl_start_capacity(1024, 8) == 256

    def test_wgl_start_capacity_ladder(self):
        assert ladder.wgl_start_capacity(64, 16) == 1024
        assert ladder.wgl_start_capacity(64, 32) == 4096
        # small windows are capped by the true subset bound 2**w
        assert ladder.wgl_start_capacity(64, 4) == 64
        # long histories nudge the floor up one rung
        assert ladder.wgl_start_capacity(4096, 16) == 2048
        # ... but never past the global ceiling
        assert ladder.wgl_start_capacity(8192, 512) \
            == ladder.MAX_WGL_CAPACITY

    def _sched_cell(self, sched, history, deadline_s=None, spec=None,
                    bucket=("wgl", "m", 64, 8)):
        from jepsen_tpu.serve.request import Cell, Request
        req = Request(history, "wgl", spec or {}, deadline_s=deadline_s)
        cell = Cell(request=req, history=history, bucket=bucket)
        return cell

    def test_start_capacity_resolution_order(self, monkeypatch):
        from jepsen_tpu.serve.scheduler import Scheduler
        h = cas_register_history(20, seed=0)
        monkeypatch.delenv("JEPSEN_TPU_WGL_CAPACITY", raising=False)
        s = Scheduler(Metrics())          # never started: pure resolution
        derived = self._sched_cell(s, h)
        assert s._start_capacity([derived], 64, 8) \
            == ladder.wgl_start_capacity(64, 8)
        # env override beats the derivation
        monkeypatch.setenv("JEPSEN_TPU_WGL_CAPACITY", "123")
        assert s._start_capacity([derived], 64, 8) == 123
        # explicit per-request capacity beats the env
        explicit = self._sched_cell(s, h, spec={"capacity": 77})
        assert s._start_capacity([explicit], 64, 8) == 77
        # a service-level fixed knob beats the derivation (but not env)
        monkeypatch.delenv("JEPSEN_TPU_WGL_CAPACITY")
        pinned = Scheduler(Metrics(), capacity=512)
        assert pinned._start_capacity([derived], 64, 8) == 512

    def test_aged_bucket_outranks_deadline_pick(self):
        import time
        from jepsen_tpu.serve.scheduler import Scheduler
        h = cas_register_history(20, seed=0)
        metrics = Metrics()
        s = Scheduler(metrics, age_s=0.01)   # never started: manual take
        old = self._sched_cell(s, h, bucket=("wgl", "m", 64, 8))
        s.offer([old], block=False, max_depth=100, timeout=None)
        time.sleep(0.05)
        urgent = self._sched_cell(s, h, deadline_s=0.5,
                                  bucket=("wgl", "m", 128, 8))
        s.offer([urgent], block=False, max_depth=100, timeout=None)
        # deadline-first would pick the urgent bucket; aging overrides
        took = s._take_group()
        assert took == [old]
        assert metrics.snapshot()["counters"]["aged_picks"] == 1
        # the remaining bucket drains normally, no second aged pick
        assert s._take_group() == [urgent]
        assert metrics.snapshot()["counters"]["aged_picks"] == 1

    def test_aging_disabled_keeps_deadline_order(self):
        import time
        from jepsen_tpu.serve.scheduler import Scheduler
        h = cas_register_history(20, seed=0)
        s = Scheduler(Metrics(), age_s=None)
        old = self._sched_cell(s, h, bucket=("wgl", "m", 64, 8))
        s.offer([old], block=False, max_depth=100, timeout=None)
        time.sleep(0.02)
        urgent = self._sched_cell(s, h, deadline_s=0.5,
                                  bucket=("wgl", "m", 128, 8))
        s.offer([urgent], block=False, max_depth=100, timeout=None)
        assert s._take_group() == [urgent]

    def test_mono_now_is_shared_and_monotonic(self):
        a = mono_now()
        b = mono_now()
        assert b >= a
        # monitor epochs and serve spans stamp off the same helper
        import jepsen_tpu.monitor as mon
        import jepsen_tpu.serve.request as req
        assert mon.mono_now is mono_now
        assert req.mono_now is mono_now
