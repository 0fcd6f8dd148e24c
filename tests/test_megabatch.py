"""The megabatch throughput path (jepsen_tpu.parallel.megabatch).

Covers lane-for-lane parity with check_batch and the CPU oracle,
packing invariance (shuffled input order and varied group sizes must
produce identical per-history verdicts and configs-explored, including
across early-retire/refill boundaries), overflow escalation, the O(1)
per-dispatch readback counters (with JAX's transfer guard armed), the
engine-cache group_reuses accounting, the serve lane ladder, and the
scheduler routing knob.  Everything runs on the CPU backend.
"""

import pytest

from jepsen_tpu.checker import wgl_cpu
from jepsen_tpu.engine import ladder
from jepsen_tpu.engine.cache import EngineCache, engine_cache_stats
from jepsen_tpu.models import CASRegister, get_model
from jepsen_tpu.parallel import megabatch as mb
from jepsen_tpu.parallel.batch import check_batch
from jepsen_tpu.parallel.megabatch import (
    SUMMARY_WIDTH, check_megabatch, megabatch_enabled, megabatch_stats,
    reset_megabatch_stats,
)
from jepsen_tpu.synth import cas_register_history, corrupt_reads


@pytest.fixture(scope="module")
def model():
    return get_model("cas-register")


def mixed_histories(n=24, seed0=900):
    """Histories of deliberately mixed length (early-retiring short lanes
    next to long ones) with every 4th refuted by a corrupted read."""
    hs = []
    for i in range(n):
        n_ops = (10, 40, 80, 25)[i % 4] + (i % 3) * 4
        h = cas_register_history(n_ops, concurrency=4, crash_p=0.01,
                                 seed=seed0 + i)
        if i % 4 == 3:
            h = corrupt_reads(h, n=1, seed=i)
        hs.append(h)
    return hs


def result_key(r):
    """The per-history facts that must be packing-invariant."""
    return (r["valid"], r.get("configs-explored"),
            (r.get("op") or {}).get("index"))


class TestParity:
    def test_matches_check_batch_and_oracle(self, model):
        hs = mixed_histories(24)
        ref = check_batch(model, hs)
        got = check_megabatch(model, hs, lanes=8)
        assert [result_key(r) for r in got] \
            == [result_key(r) for r in ref]
        for h, g in zip(hs, got):
            assert g["valid"] == wgl_cpu.check(CASRegister(), h)["valid"]
        assert sum(1 for g in got if g["valid"] is False) == 6

    def test_refuting_op_rides(self, model):
        hs = mixed_histories(8)
        got = check_megabatch(model, hs, lanes=4)
        bad = [g for g in got if g["valid"] is False]
        assert bad and all("op" in g and "index" in g["op"] for g in bad)
        assert all(g["analyzer"] == "wgl-tpu-megabatch" for g in got)

    def test_empty_and_single(self, model):
        assert check_megabatch(model, []) == []
        h = cas_register_history(30, concurrency=3, seed=1)
        (r,) = check_megabatch(model, [h])
        assert r["valid"] == wgl_cpu.check(CASRegister(), h)["valid"]


class TestPackingInvariance:
    def test_shuffle_and_group_size_fuzz(self, model):
        import random
        hs = mixed_histories(20, seed0=950)
        ref = {i: result_key(r)
               for i, r in enumerate(check_megabatch(model, hs, lanes=4))}
        # the oracle pins the verdicts the invariance is measured against
        oracle = [wgl_cpu.check(CASRegister(), h)["valid"] for h in hs]
        assert [ref[i][0] for i in range(len(hs))] == oracle
        rng = random.Random(7)
        for lanes, quantum in ((8, 1), (16, None), (64, 2)):
            order = list(range(len(hs)))
            rng.shuffle(order)
            got = check_megabatch(model, [hs[i] for i in order],
                                  lanes=lanes, refill_quantum=quantum)
            assert [result_key(r) for r in got] \
                == [ref[i] for i in order]

    def test_refill_boundaries_are_invariant(self, model, monkeypatch):
        # Tiny groups + quantum 1: every retire is a refill boundary.
        monkeypatch.setattr(mb, "MAX_LANES_PER_GROUP", 4)
        hs = mixed_histories(18, seed0=975)
        ref = [result_key(r) for r in check_batch(model, hs)]
        reset_megabatch_stats()
        got = check_megabatch(model, hs, lanes=4, refill_quantum=1)
        st = megabatch_stats()
        assert [result_key(r) for r in got] == ref
        assert st["refills"] > 0 and st["lanes_refilled"] > 0
        assert st["groups"] >= 2     # grouped vmaps, one executable


class TestEscalation:
    def test_overflow_lanes_escalate_with_parity(self, model):
        hs = mixed_histories(12, seed0=990)
        ref = [result_key(r) for r in check_batch(model, hs)]
        reset_megabatch_stats()
        got = check_megabatch(model, hs, lanes=8, capacity=8)
        assert megabatch_stats()["escalated_lanes"] > 0
        assert [result_key(r) for r in got] == ref


class TestReadbackDiscipline:
    def test_o1_summary_readback(self, model):
        hs = mixed_histories(20)
        reset_megabatch_stats()
        check_megabatch(model, hs, lanes=8, transfer_guard=True)
        st = megabatch_stats()
        # per-dispatch readback is exactly SUMMARY_WIDTH ints; everything
        # else is a (refill-amortized) harvest
        assert st["summary_ints"] == st["summary_reads"] * SUMMARY_WIDTH
        assert 0 < st["summary_reads"] <= st["dispatches"]
        assert st["harvests"] <= st["refills"] + st["groups"]
        assert st["lanes_retired"] == len(hs)

    def test_stats_reach_serve_metrics(self, model):
        from jepsen_tpu.serve.metrics import Metrics
        reset_megabatch_stats()
        check_megabatch(model, mixed_histories(8), lanes=4)
        snap = Metrics().snapshot()
        assert snap["megabatch"]["dispatches"] > 0
        assert "group_reuses" in snap["engine-cache"]


class TestGroupReuses:
    def test_lru_counts_group_reuse_separately(self):
        c = EngineCache(4)
        c.put("k", "v")
        assert c.get("k") == "v"
        assert c.get("k", group_reuse=True) == "v"
        assert c.get("missing", group_reuse=True) is None
        st = c.stats()
        assert st["hits"] == 1 and st["group_reuses"] == 1
        assert st["misses"] == 1

    def test_megabatch_groups_reuse_one_executable(self, model,
                                                   monkeypatch):
        monkeypatch.setattr(mb, "MAX_LANES_PER_GROUP", 4)
        before = engine_cache_stats()["group_reuses"]
        check_megabatch(model,
                        [cas_register_history(20, concurrency=3,
                                              seed=40 + i)
                         for i in range(16)], lanes=16)
        assert engine_cache_stats()["group_reuses"] > before


class TestLaneLadder:
    def test_mega_lane_bucket(self):
        assert ladder.mega_lane_bucket(1) == 1
        assert ladder.mega_lane_bucket(600) == 1024
        assert ladder.mega_lane_bucket(5000) == ladder.MAX_MEGA_LANES
        assert ladder.MAX_MEGA_LANES >= 512  # grouped-vmap territory

    def test_enabled_knob(self, monkeypatch):
        monkeypatch.delenv("JEPSEN_TPU_MEGABATCH", raising=False)
        assert megabatch_enabled()
        monkeypatch.setenv("JEPSEN_TPU_MEGABATCH", "0")
        assert not megabatch_enabled()
        monkeypatch.setenv("JEPSEN_TPU_MEGABATCH", "off")
        assert not megabatch_enabled()

    def test_staging_depth_knob(self, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_STAGING_DEPTH", "3")
        assert mb.staging_depth_default() == 3
        monkeypatch.setenv("JEPSEN_TPU_STAGING_DEPTH", "bogus")
        assert mb.staging_depth_default() == 2


class TestStateWidthLadder:
    """The state-width rungs of the chunk/capacity ladder: a finite
    bucket universe that the model sizing hooks land on, with every
    derived component a pure function of the bucket tuple."""

    def test_bucket_universe_is_finite(self):
        widths = list(range(1, 130)) + [200, 500, 1000, 2000, 4096]
        rungs = {ladder.state_width_bucket(w) for w in widths}
        assert rungs == {4, 8, 16, 32, 64, 128, 256, 512, 1024,
                         2048, 4096}
        assert all(r >= ladder.MIN_STATE_WIDTH_BUCKET
                   and (r & (r - 1)) == 0 for r in rungs)

    def test_derive_queue_slots_lands_on_ladder(self):
        from jepsen_tpu.engine.model_plugin import derive_queue_slots
        from jepsen_tpu.synth import queue_history
        for seed in range(8):
            h = queue_history(n_ops=10 + 7 * seed, concurrency=2,
                              seed=seed)
            slots = derive_queue_slots(h, {})["slots"]
            assert slots & (slots - 1) == 0 and slots >= 8
            # the compiled ring width (2 header + slots) quantizes onto
            # the same pow2 state ladder the chunk/capacity key on
            width = 2 + slots
            assert ladder.state_width_bucket(width) \
                == ladder.pow2_at_least(width,
                                         ladder.MIN_STATE_WIDTH_BUCKET)

    def test_chunk_and_capacity_pure_functions_of_bucket(self):
        from jepsen_tpu.engine.ladder import mega_chunk, state_capacity
        # raw widths sharing a rung derive identical chunk/capacity
        for a, b in ((5, 8), (9, 16), (17, 32), (33, 64)):
            assert ladder.state_width_bucket(a) \
                == ladder.state_width_bucket(b)
            assert mega_chunk(64, 128, a) == mega_chunk(64, 128, b)
            assert state_capacity(128, 8, a) == state_capacity(128, 8, b)
        # the register rung is undamped: exactly the PR 6 derivations
        assert mega_chunk(64, 128, 1) == ladder.batch_chunk(64, 128)
        assert state_capacity(64, 8, 1) == ladder.wgl_start_capacity(64, 8)
        # wider rungs damp monotonically and never break the floors
        caps = [state_capacity(64, 8, w) for w in (1, 8, 34, 128)]
        assert caps == sorted(caps, reverse=True)
        assert all(c >= ladder.MIN_WGL_CAPACITY for c in caps)
        chunks = [mega_chunk(64, 2048, w) for w in (1, 8, 34, 128)]
        assert chunks == sorted(chunks, reverse=True)
        assert all(c >= 64 and c % 64 == 0 for c in chunks)


class TestPluginModelParity:
    """Queue/set/opacity lanes through megabatch: lane-for-lane parity
    with check_batch AND the CPU oracle, over valid + corrupt + crash
    lanes, plus the overflow-escalation leg at a starved capacity."""

    @staticmethod
    def _families():
        from jepsen_tpu.engine.model_plugin import derive_queue_slots
        from jepsen_tpu.engine.opacity import derive_history
        from jepsen_tpu.synth import (corrupt_queue, corrupt_set,
                                      corrupt_txn_reads, queue_history,
                                      set_history, txn_history)
        qs = [queue_history(n_ops=24, concurrency=2, crash_p=0.01,
                            seed=s) for s in range(6)]
        qs[2] = corrupt_queue(qs[2], mode="lost", seed=2)
        qs[5] = corrupt_queue(qs[5], mode="duplicated", seed=5)
        slots = max(derive_queue_slots(h, {})["slots"] for h in qs)
        ss = [set_history(n_ops=24, concurrency=3, crash_p=0.01, seed=s)
              for s in range(6)]
        ss[1] = corrupt_set(ss[1], mode="phantom", seed=1)
        ss[4] = corrupt_set(ss[4], mode="lost", seed=4)
        ts = [txn_history(n_txns=12, concurrency=3, crash_p=0.01, seed=s)
              for s in range(6)]
        ts[3] = corrupt_txn_reads(ts[3], n=1, seed=3, target="ok")
        return [
            ("fifo-queue", get_model("fifo-queue", slots=slots), qs),
            ("set", get_model("set"), ss),
            ("txn-register", get_model("txn-register"),
             [derive_history(h) for h in ts]),
        ]

    def test_lane_for_lane_parity(self):
        for name, model, hs in self._families():
            ref = check_batch(model, hs)
            got = check_megabatch(model, hs, lanes=4)
            assert [result_key(r) for r in got] \
                == [result_key(r) for r in ref], name
            for i, (h, g) in enumerate(zip(hs, got)):
                oracle = wgl_cpu.check(model.cpu_model(), h)
                assert g["valid"] == oracle["valid"], (name, i)
            assert any(g["valid"] is False for g in got), name

    def test_overflow_escalation_parity(self):
        # Starved capacity: queue frontiers blow through 8 configs, so
        # lanes retire with the overflow sentinel and re-run through the
        # barrier path — verdicts must not move.
        name, model, hs = self._families()[0]
        ref = [result_key(r) for r in check_batch(model, hs)]
        reset_megabatch_stats()
        got = check_megabatch(model, hs, lanes=4, capacity=8)
        assert megabatch_stats()["escalated_lanes"] > 0
        assert [result_key(r) for r in got] == ref


class TestRoutingRegistry:
    """scheduler._mega_eligible consults the carry-descriptor registry
    (engine.plugins), never a hard-coded model family — and a family
    without a descriptor falls back to check_batch, never rejected."""

    @staticmethod
    def _sched():
        from jepsen_tpu.serve.metrics import Metrics
        from jepsen_tpu.serve.scheduler import Scheduler
        return Scheduler(metrics=Metrics(), max_lanes=8)

    def test_registered_families_are_eligible(self, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_MEGABATCH", "1")
        s = self._sched()
        for ident in (("cas-register", ()), ("fifo-queue", (16,)),
                      ("set", ()), ("txn-register", (3, 4)),
                      ("multi-register", (3, 4))):
            assert s._mega_eligible(("wgl", ident, 64, 8)), ident

    def test_unregistered_family_falls_back(self, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_MEGABATCH", "1")
        s = self._sched()
        assert not s._mega_eligible(("wgl", ("no-such-model", ()), 64, 8))
        # fallback is the barrier path, not a rejection: the group limit
        # stays a real dispatch width
        assert s._group_limit(("wgl", ("no-such-model", ()), 64, 8)) \
            == s.max_lanes

    def test_other_gates_still_hold(self, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_MEGABATCH", "1")
        s = self._sched()
        # elle cells and oversized event buckets keep the barrier path
        assert not s._mega_eligible(("elle", ("fifo-queue", ()), 64))
        assert not s._mega_eligible(
            ("wgl", ("cas-register", ()),
             ladder.MEGA_EVENTS_MAX * 2, 8))
        monkeypatch.setenv("JEPSEN_TPU_MEGABATCH", "0")
        assert not s._mega_eligible(("wgl", ("cas-register", ()), 64, 8))

    def test_plugin_model_routes_through_service(self, monkeypatch):
        from jepsen_tpu.engine.model_plugin import derive_queue_slots
        from jepsen_tpu.serve import CheckService
        from jepsen_tpu.synth import queue_history
        monkeypatch.setenv("JEPSEN_TPU_MEGABATCH", "1")
        hs = [queue_history(n_ops=20, concurrency=2, seed=200 + i)
              for i in range(4)]
        slots = max(derive_queue_slots(h, {})["slots"] for h in hs)
        model = get_model("fifo-queue", slots=slots)
        with CheckService(max_lanes=8) as svc:
            reqs = [svc.submit(h, kind="wgl", model=model) for h in hs]
            rs = [r.wait(timeout=300.0) for r in reqs]
            snap = svc.metrics.snapshot()
        assert all(r["valid"] is True for r in rs)
        assert snap["counters"].get("megabatch-dispatches", 0) > 0
        # the steady-state compile gauge rides the same snapshot
        assert snap["gauges"]["compiles-per-1k-dispatches"] is not None


class TestSchedulerRouting:
    def test_small_wgl_cells_route_megabatch(self, monkeypatch):
        from jepsen_tpu.serve import CheckService
        monkeypatch.setenv("JEPSEN_TPU_MEGABATCH", "1")
        with CheckService(max_lanes=8) as svc:
            reqs = [svc.submit(cas_register_history(30, seed=70 + i),
                               kind="wgl", model="cas-register")
                    for i in range(6)]
            rs = [r.wait(timeout=300.0) for r in reqs]
            snap = svc.metrics.snapshot()
        assert all(r["valid"] is True for r in rs)
        assert snap["counters"].get("megabatch-dispatches", 0) > 0
        assert snap["counters"].get("megabatch-lanes", 0) >= 6

    def test_kill_switch_restores_barrier_path(self, monkeypatch):
        from jepsen_tpu.serve import CheckService
        monkeypatch.setenv("JEPSEN_TPU_MEGABATCH", "0")
        with CheckService(max_lanes=8) as svc:
            r = svc.submit(cas_register_history(30, seed=80),
                           kind="wgl", model="cas-register")
            assert r.wait(timeout=300.0)["valid"] is True
            snap = svc.metrics.snapshot()
        assert snap["counters"].get("megabatch-dispatches", 0) == 0
