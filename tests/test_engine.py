"""The engine substrate: unit tests for the shared contract pieces
(cache/groups/ladder/budget/fallback/witness), the plugin registry, the
opacity reduction, and CPU-model parity fuzz for the three new drop-in
models (queue/set/opacity) — device verdicts must match the host oracles
lane for lane, corrupted histories must refute WITH a recovered witness,
and budget exhaustion must degrade to ``unknown``, never ``False``."""

import threading
import time

import pytest

from jepsen_tpu import synth
from jepsen_tpu.checker import wgl_cpu
from jepsen_tpu.checker.core import resolve_checker
from jepsen_tpu.engine import (
    CACHE, Deadline, EngineCache, MAX_LANES_PER_GROUP, WITNESS_BUDGET,
    annotate_fallback, batch_shape, bounded_group_cap, chain_entry,
    cpu_witness, exhausted_result, group_slices, next_capacity,
    refuted_result, registered_plugins, round_window,
)
from jepsen_tpu.engine import ladder, plugins
from jepsen_tpu.engine.model_plugin import derive_queue_slots
from jepsen_tpu.engine.opacity import OpacityChecker, derive_history
from jepsen_tpu.history import FAIL, History, INFO, INVOKE, OK, Op
from jepsen_tpu.models import (
    FIFOQueue, SetModel, TxnRegister, get_model,
)


# -- cache -------------------------------------------------------------------

class TestEngineCache:
    def test_lru_eviction(self):
        c = EngineCache(capacity=2)
        c.put("a", 1)
        c.put("b", 2)
        assert c.get("a") == 1          # refresh a
        c.put("c", 3)                   # evicts b, the LRU
        assert c.get("b") is None
        assert c.get("a") == 1 and c.get("c") == 3
        assert c.stats()["evictions"] == 1

    def test_stats_and_group_reuse(self):
        c = EngineCache(capacity=4)
        c.put("k", "v")
        assert c.get("missing") is None
        assert c.get("k") == "v"
        assert c.get("k", group_reuse=True) == "v"
        s = c.stats()
        assert s["hits"] == 1 and s["misses"] == 1
        assert s["group_reuses"] == 1 and s["size"] == 1

    def test_shared_instance_is_engine_cache(self):
        # One process-wide cache: batch and single-engine keys coexist.
        assert isinstance(CACHE, EngineCache)

    def test_single_and_batch_keys_share_the_substrate_cache(self):
        # The wgl single-history tier and the batch tier both key into
        # engine.cache.CACHE (prefixes "singlev"/"batchv") — the point of
        # the extraction.  Run one check through each and look for both.
        from jepsen_tpu.parallel.batch import _CACHE
        assert _CACHE is CACHE
        h = synth.queue_history(n_ops=10, concurrency=2, seed=0)
        resolve_checker("linearizable-queue").check(None, h)
        prefixes = {k[0] for k in CACHE._d}
        assert "singlev" in prefixes


# -- groups ------------------------------------------------------------------

class TestGroups:
    def test_no_split_under_cap(self):
        assert list(group_slices(5, 8)) == [(0, 5, False)]

    def test_split_and_reuse_flags(self):
        out = list(group_slices(1100, 512))
        assert out == [(0, 512, False), (512, 1024, True),
                       (1024, 1100, True)]

    def test_cap_is_512(self):
        # bool-scatter vmap miscompile at >=1024 lanes; 512 is the pinned
        # safe cap for every grouped engine.
        assert MAX_LANES_PER_GROUP == 512

    def test_bounded_group_cap(self):
        assert bounded_group_cap(1 << 20, 4096) == 256
        assert bounded_group_cap(100, 1000) == 1      # floor at 1
        assert bounded_group_cap(1 << 30, 1) == 512   # ceiling at cap


# -- ladder ------------------------------------------------------------------

class TestLadder:
    def test_ladder_defines_its_own_rungs(self):
        # one module defines the ladder: plain definitions, no lazy
        # re-export, and the old serve-side path is gone
        import importlib.util
        assert ladder.pow2_at_least.__module__ == ladder.__name__
        assert ladder.wgl_bucket.__module__ == ladder.__name__
        assert "__getattr__" not in vars(ladder)
        assert importlib.util.find_spec("jepsen_tpu.serve.buckets") is None

    def test_round_window(self):
        assert round_window(1) == 8
        assert round_window(9) == 12
        assert round_window(12) == 12

    def test_next_capacity(self):
        assert next_capacity(256, 65536) == 2048
        assert next_capacity(65536, 65536) is None

    def test_batch_shape_respects_window_floor(self):
        h = synth.queue_history(n_ops=12, concurrency=2, seed=0)
        from jepsen_tpu.checker.wgl_tpu import prepare
        m = get_model("fifo-queue", slots=8)
        preps = [prepare(h, m)]
        w0, _, _ = batch_shape(preps)
        w16, _, _ = batch_shape(preps, window_floor=16)
        assert w16 >= 16 and w16 >= w0

    def test_queue_slots_derivation_is_bucketed(self):
        h = synth.queue_history(n_ops=40, concurrency=3, seed=0)
        slots = derive_queue_slots(h, {})["slots"]
        assert slots >= 8 and slots & (slots - 1) == 0  # pow2, floored
        assert derive_queue_slots(h, {"slots": 4}) == {}  # explicit wins


# -- budget ------------------------------------------------------------------

class TestDeadline:
    def test_none_budget_never_expires(self):
        d = Deadline.after(None)
        assert d.remaining() is None
        assert not d.expired()
        assert d.search_budget() is None

    def test_finite_budget(self):
        d = Deadline.after(100.0)
        r = d.remaining()
        assert 0 < r <= 100.0
        assert not d.expired()
        b = d.search_budget()
        assert b is not None and b.deadline is not None

    def test_expiry(self):
        d = Deadline.after(0.0)
        time.sleep(0.001)
        assert d.expired()
        assert d.remaining() <= 0

    def test_exhausted_result_is_unknown_never_false(self):
        res = exhausted_result("wgl-tpu-batch", "capacity exceeded at 64",
                               lanes=3)
        assert res["valid"] == "unknown"
        assert res["valid"] is not False
        assert res["analyzer"] == "wgl-tpu-batch" and res["lanes"] == 3


# -- fallback ----------------------------------------------------------------

class TestFallback:
    def test_chain_entry(self):
        e = chain_entry("wgl-tpu", RuntimeError("xla oom"))
        assert e == {"solver": "wgl-tpu", "error": "xla oom",
                     "error-type": "RuntimeError"}

    def test_annotate_fallback(self):
        entry = chain_entry("wgl-tpu", ValueError("boom"))
        res = {"valid": True}
        annotate_fallback(res, "wgl-tpu", "wgl-cpu", entry, [entry])
        assert res["fallback"]["from"] == "wgl-tpu"
        assert res["fallback"]["to"] == "wgl-cpu"
        assert res["fallback-chain"] == [entry]


# -- witness -----------------------------------------------------------------

class TestWitness:
    def test_refuted_result_carries_the_op(self):
        op = Op(process=0, type=OK, f="dequeue", value=7, index=3)
        res = refuted_result("wgl-tpu-batch", op, 123)
        assert res["valid"] is False
        assert res["op"]["value"] == 7
        assert res["configs-explored"] == 123

    def test_cpu_witness_recovers_final_configs(self):
        h = synth.queue_history(n_ops=20, concurrency=2, seed=5)
        bad = synth.corrupt_queue(h, mode="lost", seed=6)
        m = get_model("fifo-queue", slots=32)
        # find the refuting op the device would flag: host oracle verdict
        host = wgl_cpu.check(FIFOQueue(), bad)
        assert host["valid"] is False
        w = cpu_witness(m, bad, Op(**{**host["op"],
                                      "type": host["op"]["type"]}))
        assert w["valid"] is False
        assert "final-configs" in w

    def test_witness_budget_degrades_witness_not_verdict(self):
        h = synth.queue_history(n_ops=30, concurrency=5, seed=7)
        bad = synth.corrupt_queue(h, mode="lost", seed=8)
        host = wgl_cpu.check(FIFOQueue(), bad)
        m = get_model("fifo-queue", slots=32)
        w = cpu_witness(m, bad, Op(**host["op"]), budget=1)
        assert w == {"error": "witness search exceeded budget"}
        assert WITNESS_BUDGET > 0


# -- plugin registry ---------------------------------------------------------

class TestPluginRegistry:
    def test_builtins_registered(self):
        names = registered_plugins()
        for want in ("linearizable-queue", "linearizable-set", "opacity"):
            assert want in names

    def test_resolve_through_checker_registry(self):
        for name in ("linearizable-queue", "linearizable-set", "opacity"):
            c = resolve_checker(name)
            assert hasattr(c, "check")

    def test_plugin_info(self):
        info = plugins.plugin_info("linearizable-queue")
        assert info["model"] == "fifo-queue"
        assert info["doc"]

    def test_register_custom_plugin(self):
        reg = {}
        plugins.register_model_plugin(
            "test-unordered-queue", "fifo-queue",
            lambda name, factory: reg.setdefault(name, factory),
            doc="test-only", model_kw={"slots": 8})
        assert "test-unordered-queue" in reg
        checker = reg["test-unordered-queue"]()
        h = synth.queue_history(n_ops=10, concurrency=2, seed=0)
        assert checker.check(None, h)["valid"] is True
        plugins._PLUGINS.pop("test-unordered-queue", None)


# -- opacity reduction -------------------------------------------------------

class TestOpacityReduction:
    def _pair(self, p, t, mops, typ=OK, filled=None):
        return [Op(process=p, type=INVOKE, f="txn", value=mops, time=t),
                Op(process=p, type=typ, f="txn",
                   value=filled if filled is not None else mops,
                   time=t + 1)]

    def test_committed_passes_through(self):
        ops = self._pair(0, 0, [["w", 0, 1], ["r", 0, 1]])
        d = derive_history(History(ops, reindex=True))
        assert [o.f for o in d] == ["txn", "txn"]

    def test_aborted_becomes_readonly_ok(self):
        ops = self._pair(0, 0, [["r", 0, None]], typ=FAIL,
                         filled=[["r", 0, 5], ["w", 1, 9]])
        d = derive_history(History(ops, reindex=True))
        assert [o.f for o in d] == ["txn-ro", "txn-ro"]
        assert d.ops[1].type == OK
        assert d.ops[1].value == [["r", 0, 5]]   # write stripped

    def test_read_own_write_is_not_constraining(self):
        # The aborted txn's read saw its own discarded write: it says
        # nothing about global state and must NOT survive the reduction
        # (keeping it would wrongly refute a fine history).
        ops = self._pair(0, 0, [["w", 0, 3], ["r", 0, 3]], typ=FAIL)
        d = derive_history(History(ops, reindex=True))
        assert len(d) == 0                       # nothing constrains

    def test_unconstraining_abort_dropped_entirely(self):
        ops = (self._pair(0, 0, [["w", 0, 1]], typ=FAIL)
               + self._pair(1, 10, [["w", 0, 2]]))
        d = derive_history(History(ops, reindex=True))
        assert len(d) == 2 and all(o.f == "txn" for o in d)

    def test_crashed_txn_untouched(self):
        ops = [Op(process=0, type=INVOKE, f="txn", value=[["w", 0, 1]],
                  time=0),
               Op(process=0, type=INFO, f="txn", value=[["w", 0, 1]],
                  time=1, error="crashed")]
        d = derive_history(History(ops, reindex=True))
        assert [o.type for o in d] == [INVOKE, INFO]

    def test_opacity_stricter_than_committed_linearizability(self):
        # The distinguishing case: an aborted txn observed an impossible
        # value.  Committed-only linearizability passes; opacity refutes.
        ops = (self._pair(0, 0, [["w", 0, 1]])
               + self._pair(1, 10, [["r", 0, None]], typ=FAIL,
                            filled=[["r", 0, 2]]))
        h = History(ops, reindex=True)
        committed = History([o for o in h
                             if not (o.f == "txn" and (o.type == FAIL or
                                     h.pair_index()[o.index] >= 0 and
                                     h.ops[int(h.pair_index()[o.index])]
                                     .type == FAIL))], reindex=True)
        assert wgl_cpu.check(TxnRegister(), derive_history(committed)
                             )["valid"] is True
        res = OpacityChecker().check(None, h)
        assert res["valid"] is False
        assert res["checker"] == "opacity"
        assert "arXiv:1610.01004" in res["reduction"]


# -- CPU-model parity fuzz (the acceptance gate) ------------------------------

QUEUE_SEEDS = [11, 12, 13]
SET_SEEDS = [21, 22, 23]
TXN_SEEDS = [31, 32, 33]


class TestQueueParity:
    @pytest.mark.parametrize("seed", QUEUE_SEEDS)
    def test_valid_parity(self, seed):
        # concurrency 2: the queue's wide ring state makes each capacity
        # rung a fresh compile, and conc-3 frontiers escalate several
        # rungs per seed — the deep fuzz lives in scripts/engine_smoke.py
        h = synth.queue_history(n_ops=32, concurrency=2, seed=seed)
        dev = resolve_checker("linearizable-queue").check(None, h)
        host = wgl_cpu.check(FIFOQueue(), h)
        assert dev["valid"] is True and host["valid"] is True
        assert dev["analyzer"] == "wgl-tpu"

    @pytest.mark.parametrize("seed,mode", [(11, "lost"), (12, "duplicated"),
                                           (13, "lost")])
    def test_corrupted_parity_with_witness(self, seed, mode):
        h = synth.queue_history(
            n_ops=40, concurrency=1 if mode != "lost" else 3, seed=seed)
        bad = synth.corrupt_queue(h, mode=mode, seed=seed + 100)
        dev = resolve_checker("linearizable-queue").check(None, bad)
        host = wgl_cpu.check(FIFOQueue(), bad)
        assert dev["valid"] is False and host["valid"] is False
        assert "op" in dev                     # the lane's flag
        w = dev.get("witness")                 # the CPU's recovery
        assert w and w["valid"] is False and "final-configs" in w

    def test_reordered_refutes_fifo(self):
        h = synth.queue_history(n_ops=30, concurrency=1, seed=14)
        bad = synth.corrupt_queue(h, mode="reordered", seed=15)
        dev = resolve_checker("linearizable-queue").check(None, bad)
        assert dev["valid"] is False


class TestSetParity:
    @pytest.mark.parametrize("seed", SET_SEEDS)
    def test_valid_parity(self, seed):
        h = synth.set_history(n_ops=40, concurrency=3, seed=seed)
        dev = resolve_checker("linearizable-set").check(None, h)
        host = wgl_cpu.check(SetModel(), h)
        assert dev["valid"] is True and host["valid"] is True

    @pytest.mark.parametrize("seed,mode", [(21, "phantom"), (22, "lost")])
    def test_corrupted_parity_with_witness(self, seed, mode):
        conc = 3 if mode == "phantom" else 1
        h = synth.set_history(n_ops=40, concurrency=conc, seed=seed)
        bad = synth.corrupt_set(h, mode=mode, seed=seed + 100)
        dev = resolve_checker("linearizable-set").check(None, bad)
        host = wgl_cpu.check(SetModel(), bad)
        assert dev["valid"] is False and host["valid"] is False
        w = dev.get("witness")
        assert w and w["valid"] is False and "final-configs" in w


class TestOpacityParity:
    @pytest.mark.parametrize("seed", TXN_SEEDS)
    def test_valid_parity(self, seed):
        h = synth.txn_history(n_txns=30, concurrency=3, seed=seed)
        dev = resolve_checker("opacity").check(None, h)
        host = wgl_cpu.check(TxnRegister(), derive_history(h))
        assert dev["valid"] is True and host["valid"] is True
        assert dev["derived-ops"] <= len(h.client_ops())

    @pytest.mark.parametrize("seed", TXN_SEEDS)
    def test_corrupted_abort_parity(self, seed):
        h = synth.txn_history(n_txns=30, concurrency=3, seed=seed,
                              abort_p=0.4)
        bad = synth.corrupt_txn_reads(h, target="fail", seed=seed + 100)
        dev = resolve_checker("opacity").check(None, bad)
        host = wgl_cpu.check(TxnRegister(), derive_history(bad))
        assert dev["valid"] is False and host["valid"] is False


# -- budget exhaustion: unknown, never false ---------------------------------

class TestBudgetExhaustion:
    def test_single_engine_capacity_ceiling(self):
        from jepsen_tpu.checker import wgl_tpu
        h = synth.queue_history(n_ops=30, concurrency=5, crash_p=0.05,
                                seed=41)
        m = get_model("fifo-queue", slots=32)
        res = wgl_tpu.check(m, h, capacity=2, max_capacity=2)
        # A VALID history under an impossible budget must never read as
        # refuted: either it still proves True or degrades to unknown.
        assert res["valid"] is not False

    def test_batch_engine_capacity_ceiling(self):
        from jepsen_tpu.parallel.batch import check_batch
        hs = [synth.queue_history(n_ops=30, concurrency=5, crash_p=0.05,
                                  seed=s) for s in (42, 43)]
        m = get_model("fifo-queue", slots=32)
        out = check_batch(m, hs, capacity=2, max_capacity=2,
                          window_floor=8)
        for res in out:
            assert res["valid"] is not False

    def test_checker_budget_opt_passes_through(self):
        h = synth.queue_history(n_ops=20, concurrency=2, seed=44)
        c = resolve_checker({"name": "linearizable-queue",
                             "max_capacity": 65536})
        assert c.check(None, h)["valid"] is True


# -- fallback chain end-to-end ------------------------------------------------

class TestFallbackEndToEnd:
    def test_device_crash_annotated_and_host_decides(self, monkeypatch):
        from jepsen_tpu.checker import linearizable, wgl_tpu

        def boom(*a, **kw):
            raise RuntimeError("synthetic device loss")

        monkeypatch.setattr(wgl_tpu, "check", boom)
        h = synth.queue_history(n_ops=20, concurrency=2, seed=51)
        res = resolve_checker("linearizable-queue").check(None, h)
        assert res["valid"] is True              # host decided
        assert res["fallback"]["from"] == "wgl-tpu"
        assert res["fallback-chain"][0]["error-type"] == "RuntimeError"

    def test_cancel_event_degrades_to_unknown(self):
        from jepsen_tpu.checker import wgl_tpu
        h = synth.queue_history(n_ops=40, concurrency=3, seed=52)
        ev = threading.Event()
        ev.set()
        m = get_model("fifo-queue", slots=64)
        res = wgl_tpu.check(m, h, cancel=ev)
        assert res["valid"] is not False
