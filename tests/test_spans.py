"""Spans inside the checker (jepsen_tpu.obs.recorder.span): the primitive
(nesting, parents across a thread, the root's trace id, the off path, an
exception, adoption of a request's ids, the Chrome export), the names the
offline path emits from ``core.analyze`` down to a dispatch, the
benchmark's ``program_span`` reader on a hand-made span list, and the
``jax.named_scope`` names on the engine's phases; the same for the
list-append path (``elle.*``) down to the closure kernel's scopes.
"""

import importlib.util
import os
import sys
import threading
from collections import Counter

import jax
import jax.numpy as jnp
import pytest

from jepsen_tpu import core, independent
from jepsen_tpu.checker import wgl_tpu
from jepsen_tpu.checker.linearizable import linearizable
from jepsen_tpu.history import History
from jepsen_tpu.models import get_model
from jepsen_tpu.obs import hist, recorder as rec_mod
from jepsen_tpu.obs.recorder import adopt, carry, instant, span
from jepsen_tpu.synth import cas_register_history, corrupt_reads

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: what one ``wgl_tpu.check`` emits, whatever the history
CHECK_SPANS = {"drivers.check", "prepare", "drivers.stage", "drivers.rung",
               "drivers.dispatch", "drivers.poll"}

#: what a first use adds to any of them (tests/test_first_use.py)
COMPILE_EVENTS = {"compile.first_call", "compile.trace", "compile.lower",
                  "compile.load"}

#: what ``drivers.check`` opens and closes with (docs/observability.md)
CHECK_CLOSES_WITH = {"events", "chunk", "window", "gwords", "max_capacity",
                     "dispatches", "discarded", "grows", "shrinks",
                     "resumes", "continued", "poll_max_s", "events_consumed",
                     "events_consumed_16k", "cap_events", "peak_events"}


def by_name(events):
    out = {}
    for e in events:
        out.setdefault(e["name"], []).append(e)
    return out


def parent_of(events, e):
    ids = {x["span-id"]: x for x in events if "span-id" in x}
    return ids.get(e.get("parent-span-id"))


class TestPrimitive:
    def test_nesting_parents_and_one_trace_id(self, rec):
        with span("a", n=1) as a:
            with span("b"):
                with span("c"):
                    instant("tick", why="x")
            a.set(done=True)
        evs = rec.snapshot()
        ev = {e["name"]: e for e in evs}
        assert [e["name"] for e in evs] == ["tick", "c", "b", "a"]
        assert "parent-span-id" not in ev["a"]
        assert ev["b"]["parent-span-id"] == ev["a"]["span-id"]
        assert ev["c"]["parent-span-id"] == ev["b"]["span-id"]
        assert ev["tick"]["parent-span-id"] == ev["c"]["span-id"]
        assert "dur-s" not in ev["tick"] and "span-id" not in ev["tick"]
        assert len({e["trace-id"] for e in evs}) == 1
        assert ev["a"]["args"] == {"n": 1, "done": True}
        # a span starts where it was opened, not where it was recorded
        assert ev["a"]["ts"] <= ev["b"]["ts"] <= ev["c"]["ts"]
        assert ev["a"]["dur-s"] >= ev["b"]["dur-s"] >= ev["c"]["dur-s"]
        assert a.dur_s == ev["a"]["dur-s"]

    def test_two_roots_two_trace_ids(self, rec):
        with span("r1"):
            pass
        with span("r2"):
            pass
        a, b = rec.snapshot()
        assert a["trace-id"] != b["trace-id"]

    @pytest.mark.parametrize("carried", [True, False])
    def test_parent_across_a_thread(self, rec, carried):
        def work():
            with span("child"):
                pass
        with span("outer"):
            t = threading.Thread(target=carry(work) if carried else work)
            t.start()
            t.join(timeout=30)
            assert not t.is_alive()
        ev = {e["name"]: e for e in rec.snapshot()}
        assert ev["child"]["tid"] != ev["outer"]["tid"]
        if carried:
            assert ev["child"]["parent-span-id"] == ev["outer"]["span-id"]
            assert ev["child"]["trace-id"] == ev["outer"]["trace-id"]
        else:       # a bare thread starts from an empty context: a new root
            assert "parent-span-id" not in ev["child"]
            assert ev["child"]["trace-id"] != ev["outer"]["trace-id"]

    def test_off_records_nothing_and_mints_no_id(self, rec, monkeypatch):
        def boom():
            raise AssertionError("an id was minted with the recorder off")
        rec.disable()
        monkeypatch.setattr(rec_mod, "new_trace_id", boom)
        monkeypatch.setattr(rec_mod, "new_span_id", boom)
        monkeypatch.setattr(rec_mod, "mono_now", boom)
        with span("quiet", n=1) as sp:
            sp.set(more=2)
            instant("tick")
        assert rec.snapshot() == [] and rec.stats()["recorded"] == 0
        assert sp.dur_s == 0.0 and sp.args == {"n": 1}

    def test_exception_still_closes_the_span(self, rec):
        with pytest.raises(KeyError):
            with span("outer"):
                with span("breaks"):
                    raise KeyError("x")
        ev = {e["name"]: e for e in rec.snapshot()}
        assert ev["breaks"]["parent-span-id"] == ev["outer"]["span-id"]
        with span("after"):     # the context is back at the root
            pass
        assert "parent-span-id" not in rec.snapshot()[-1]

    def test_adopt_and_bare_records_join_the_open_span(self, rec):
        with adopt("f" * 16, "0" * 8):
            with span("served"):
                rec.record("fission", "split", dur_s=0.001)
        rec.record("retry", "outside")
        split, served, outside = rec.snapshot()
        assert served["trace-id"] == "f" * 16
        assert served["parent-span-id"] == "0" * 8
        assert split["parent-span-id"] == served["span-id"]
        assert split["trace-id"] == "f" * 16
        assert "trace-id" not in outside and "parent-span-id" not in outside

    def test_chrome_events_carry_the_parent(self, rec):
        with span("a"):
            with span("b", cap=4):
                pass
        b, a = rec.chrome_events()
        assert b["ph"] == "X" and b["cat"] == "span" and b["name"] == "b"
        assert b["args"]["parent-span-id"] == a["args"]["span-id"]
        assert b["args"]["cap"] == 4
        assert a["ts"] <= b["ts"] and a["dur"] >= b["dur"]

    def test_first_call_is_a_span_and_a_histogram(self, rec):
        fn = hist.timed_first_call(lambda x: x + 1, "compile:test:spans")
        assert fn(1) == 2 and fn(2) == 3
        evs = rec.snapshot()
        assert [e["name"] for e in evs] == ["compile.first_call"]
        assert evs[0]["args"] == {"shape": "compile:test:spans"}
        assert hist.compile_hist_stats()["compile:test:spans"]["count"] == 1

    def test_jtpu_trace_is_gone(self):
        import inspect
        assert "JTPU_TRACE" not in inspect.getsource(wgl_tpu)
        assert not hasattr(rec_mod, "CATEGORIES")

    def test_the_compiled_engine_reads_no_environment(self):
        import inspect
        from jepsen_tpu.ops import dedup
        for mod in (wgl_tpu, dedup):
            assert "environ" not in inspect.getsource(mod), mod.__name__


class TestOfflinePath:
    """One ``core.analyze`` with the recorder on: the table of span names
    in docs/observability.md and PERF.md section 3 is the contract."""

    @pytest.fixture(scope="class")
    def model(self):
        return get_model("cas-register")

    def test_register_history_span_names_and_tree(self, rec, model):
        h = cas_register_history(120, concurrency=4, crash_p=0.01, seed=3)
        res = core.analyze({"checker": linearizable(model)}, h)
        assert res["valid"] is True
        evs = rec.snapshot()
        names = by_name(evs)
        assert set(names) - COMPILE_EVENTS == \
            CHECK_SPANS | {"entry.analyze"}
        root, = names["entry.analyze"]
        assert "parent-span-id" not in root
        assert {e["trace-id"] for e in evs} == {root["trace-id"]}
        check, = names["drivers.check"]
        assert parent_of(evs, check) is root
        for n in ("prepare", "drivers.stage", "drivers.rung"):
            assert parent_of(evs, names[n][0]) is check, n
        for n in ("drivers.dispatch", "drivers.poll"):
            assert parent_of(evs, names[n][0])["name"] == "drivers.rung", n
        assert check["args"]["dispatches"] == len(names["drivers.dispatch"])
        assert check["args"]["events"] > 0 and check["args"]["chunk"] > 0
        assert names["drivers.stage"][0]["args"]["bytes"] > 0
        assert set(names["drivers.poll"][0]["args"]) == {
            "pos", "cap", "peak", "consumed", "overflow"}
        # the spans under the root account for the call
        assert check["dur-s"] >= 0.9 * root["dur-s"]

    def test_keyed_history_span_names_and_witness(self, rec, model):
        lanes = [cas_register_history(40, concurrency=4, crash_p=0.005,
                                      seed=100 + i) for i in range(8)]
        lanes[0] = corrupt_reads(lanes[0], n=1, seed=0)
        keyed = History(
            [op.with_(process=op.process + 10 * k,
                      value=independent.tuple_(k, op.value))
             for k, h in enumerate(lanes) for op in h], reindex=True)
        res = core.analyze(
            {"checker": independent.checker(linearizable(model))}, keyed)
        assert res["valid"] is False and res["failures"] == [0]
        evs = rec.snapshot()
        names = by_name(evs)
        assert set(names) - COMPILE_EVENTS == {
            "entry.analyze", "entry.split", "entry.rederive",
            "drivers.check_batch", "drivers.run_lanes", "prepare",
            "drivers.stage", "drivers.dispatch", "drivers.poll",
            "witness.cpu"}
        root, = names["entry.analyze"]
        assert {e["trace-id"] for e in evs} == {root["trace-id"]}
        for n in ("entry.split", "drivers.check_batch", "entry.rederive"):
            assert parent_of(evs, names[n][0]) is root, n
        assert names["entry.split"][0]["args"] == {
            "entries": len(keyed), "keys": 8}
        assert names["drivers.check_batch"][0]["args"] == {"lanes": 8}
        lanes_pass = names["drivers.run_lanes"][0]
        assert parent_of(evs, lanes_pass)["name"] == "drivers.check_batch"
        assert lanes_pass["args"]["lanes"] == 8
        # the batch's lanes and the host oracle's own: a refuted key is
        # searched once, so no single-history prepare
        assert Counter(parent_of(evs, e)["name"]
                       for e in names["prepare"]) == {
            "drivers.check_batch": 8, "witness.cpu": 1}
        rederive, = names["entry.rederive"]
        assert rederive["args"] == {"key": 0, "confirmed": True}
        witness, = names["witness.cpu"]
        assert parent_of(evs, witness) is rederive
        assert witness["args"]["prefix"] > 0
        assert witness["args"]["configs"] > 0
        # nothing of a device search under the host's re-derivation
        for e in evs:
            if e["name"].startswith("drivers."):
                up = parent_of(evs, e)
                while up is not None:
                    assert up is not rederive, e["name"]
                    up = parent_of(evs, up)
        leaf = res["results"][0]
        assert leaf["analyzer"] == "wgl-tpu-batch"
        assert leaf["witness"]["valid"] is False

    def test_batch_retries_are_instants_and_a_pass_closes_with_counts(
            self, rec, model):
        """A batch that starts at capacity 4: every lane sent up a rung is
        one ``drivers.lane_retry`` under ``drivers.check_batch``, each pass
        one ``drivers.run_lanes`` that closes with what it dispatched, and
        ``batch_stats()`` grows by the same sums."""
        from jepsen_tpu.parallel import batch_stats, check_batch
        lanes = [cas_register_history(40, concurrency=4, crash_p=0.02,
                                      seed=100 + i) for i in range(6)]
        before = batch_stats()
        res = check_batch(model, lanes, capacity=4, max_capacity=4096)
        assert [r["valid"] for r in res] == [True] * 6
        evs = rec.snapshot()
        names = by_name(evs)
        assert set(names) - COMPILE_EVENTS == {
            "drivers.check_batch", "prepare", "drivers.run_lanes",
            "drivers.stage", "drivers.dispatch", "drivers.poll",
            "drivers.lane_retry"}
        root, = names["drivers.check_batch"]
        passes, retries = names["drivers.run_lanes"], \
            names["drivers.lane_retry"]
        assert len(passes) >= 2 and passes[0]["args"]["lanes"] == 6
        for e in retries:
            assert parent_of(evs, e) is root and "dur-s" not in e
            assert set(e["args"]) == {"lane", "cap_from", "cap_to"}
            assert e["args"]["cap_to"] == 8 * e["args"]["cap_from"]
        assert len(retries) == sum(p["args"]["lanes"] for p in passes[1:])
        for p in passes:
            assert set(p["args"]) == {"lanes", "cap", "dispatches",
                                      "events_useful", "events_dispatched"}
            assert p["args"]["dispatches"] == sum(
                parent_of(evs, d) is p for d in names["drivers.dispatch"])
            # a pass in which every lane overflowed has nothing useful
            assert 0 <= p["args"]["events_useful"] \
                <= p["args"]["events_dispatched"] > 0
        assert passes[-1]["args"]["events_useful"] > 0
        stats = batch_stats()
        assert set(stats) == {"events_useful", "events_dispatched"}
        for k in stats:
            assert stats[k] - before[k] == sum(p["args"][k] for p in passes)

    def test_a_lane_that_leaves_for_fission_is_no_retry(self, rec, model,
                                                        monkeypatch):
        """Past the fission threshold the lanes that still overflow leave
        the batch for ``split_check``: no ``drivers.lane_retry``, no second
        pass."""
        from jepsen_tpu.engine import fission
        from jepsen_tpu.parallel import check_batch
        monkeypatch.setenv("JTPU_FISSION_THRESHOLD", "4")
        left = []
        monkeypatch.setattr(
            fission, "split_check",
            lambda model, h, **kw: left.append(h) or {
                "valid": "unknown", "analyzer": fission.ANALYZER})
        lanes = [cas_register_history(40, concurrency=4, crash_p=0.02,
                                      seed=100 + i) for i in range(3)]
        res = check_batch(model, lanes, capacity=4, max_capacity=4096,
                          fission=True)
        names = by_name(rec.snapshot())
        assert "drivers.lane_retry" not in names
        assert len(names["drivers.run_lanes"]) == 1
        assert [r["analyzer"] for r in res].count(fission.ANALYZER) \
            == len(left) > 0

    def test_discards_counted_once_each(self, rec, model):
        """A ladder that climbs from 4: every speculative chunk a grow
        throws away is one ``drivers.discard``, and the ``drivers.check``
        span closes with their number."""
        h = cas_register_history(120, concurrency=4, crash_p=0.03, seed=5)
        res = wgl_tpu.check(model, h, capacity=4, chunk=32)
        assert res["valid"] is True
        names = by_name(rec.snapshot())
        did = names["drivers.check"][0]["args"]
        assert did["grows"] >= 1 and did["discarded"] >= 1
        assert len(names["drivers.discard"]) == did["discarded"]
        assert Counter(e["args"]["why"] for e in names["drivers.discard"]) \
            == {"grow": did["discarded"]}
        assert len(names["drivers.rung"]) == 1 + did["grows"] \
            + did["shrinks"]
        assert [e["args"]["cap"] for e in names["drivers.rung"]][-1] == \
            did["max_capacity"] == res["max-capacity-reached"]
        assert len(names["drivers.dispatch"]) == did["dispatches"]
        assert did["poll_max_s"] == max(
            e["dur-s"] for e in names["drivers.poll"])
        # the sums themselves: tests/test_multireg_config.py
        assert set(did) == CHECK_CLOSES_WITH
        assert 0 < did["peak_events"] <= did["cap_events"]

    def test_recorder_off_same_verdict_nothing_recorded(self, rec, model):
        h = cas_register_history(120, concurrency=4, crash_p=0.01, seed=3)
        rec.disable()
        res = core.analyze({"checker": linearizable(model)}, h)
        assert res["valid"] is True and rec.snapshot() == []


class TestEllePath:
    """One ``core.analyze`` of a list-append history under the workload's
    own checker (``workloads.cycle.append_workload``), recorder on: the
    ``elle.*`` rows of docs/observability.md and PERF.md section 3."""

    ELLE_SPANS = {"elle.analyze", "elle.encode", "elle.pack",
                  "elle.dispatch", "elle.anomalies", "elle.readback",
                  "elle.render"}

    @pytest.fixture(scope="class")
    def checker(self):
        from jepsen_tpu.workloads.cycle import append_workload
        return append_workload(
            consistency_models=("strict-serializable",))["checker"]

    def test_clean_history_span_names_and_tree(self, rec, checker):
        from jepsen_tpu.synth import list_append_history
        h = list_append_history(60, seed=1)
        res = core.analyze({"checker": checker}, h)
        assert res["valid"] is True and res["analyzer"] == "elle-tpu"
        evs = rec.snapshot()
        names = by_name(evs)
        assert set(names) - COMPILE_EVENTS == \
            self.ELLE_SPANS | {"entry.analyze"}
        root, = names["entry.analyze"]
        assert {e["trace-id"] for e in evs} == {root["trace-id"]}
        for n in self.ELLE_SPANS:
            span_, = names[n]
            assert parent_of(evs, span_) is root, n
        order = sorted(self.ELLE_SPANS, key=lambda n: names[n][0]["ts"])
        # the second half of the host pass follows the dispatch: the
        # closures run under it
        assert order == ["elle.analyze", "elle.encode", "elle.pack",
                         "elle.dispatch", "elle.anomalies", "elle.readback",
                         "elle.render"]
        assert names["elle.analyze"][0]["args"] == {
            "lanes": 1, "workload": "list-append"}
        assert names["elle.anomalies"][0]["args"] == {"lanes": 1}
        pack = names["elle.pack"][0]["args"]
        assert set(pack) == {"lanes", "n_pad", "e_pad", "bytes"}
        assert pack["n_pad"] % 32 == 0 and pack["e_pad"] % 64 == 0
        assert names["elle.dispatch"][0]["args"] == {
            "lanes": 1, "n_pad": pack["n_pad"], "e_pad": pack["e_pad"]}
        assert names["elle.readback"][0]["args"] == {
            "group": 0, "lanes": 1, "flags_set": 0}
        assert names["elle.render"][0]["args"] == {"txns": res["count"]}
        # a first call of the shape, if this process had not made it yet
        for e in names.get("compile.first_call", []):
            assert e["args"]["shape"] == \
                f"compile:elle:n{pack['n_pad']}:rt1"
            assert parent_of(evs, e)["name"] == "elle.dispatch"

    def test_cyclic_history_recovers_under_a_span(self, rec, checker):
        from jepsen_tpu.synth import list_append_history
        h = list_append_history(60, seed=1, anomaly_p=0.3)
        res = core.analyze({"checker": checker}, h)
        assert res["valid"] is False
        names = by_name(rec.snapshot())
        assert set(names) - COMPILE_EVENTS == \
            self.ELLE_SPANS | {"entry.analyze", "elle.recover"}
        recover, = names["elle.recover"]
        assert recover["args"] == {"txns": res["count"], "realtime": True,
                                   "cyclic": True, "truncated": False}
        assert names["elle.readback"][0]["args"]["flags_set"] > 0
        assert recover["ts"] < names["elle.render"][0]["ts"]

    def test_recorder_off_same_result_nothing_recorded(self, rec, checker):
        from jepsen_tpu.synth import list_append_history
        h = list_append_history(60, seed=1)
        rec.disable()
        res = core.analyze({"checker": checker}, h)
        assert res["valid"] is True and rec.snapshot() == []


# -- the benchmark's reader: plain arithmetic on (name, start, duration) ------

@pytest.fixture(scope="module")
def program_span():
    """``benchmark/readers/program_span.py``, imported by path with the
    benchmark's directory on ``sys.path`` as ``benchmark/run.py`` has it."""
    bench = os.path.join(ROOT, "benchmark")
    sys.path.insert(0, bench)
    try:
        spec = importlib.util.spec_from_file_location(
            "program_span_under_test",
            os.path.join(bench, "readers", "program_span.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(bench)
    return mod


def _spans():
    """Two calls in a window of 10 s (ns on the axis).  Call 1: check
    1-5 s holding prepare 1-2 s and two polls 2-3 s, 3.5-4.5 s.  Call 2:
    check 6-9 s holding one poll 7-9 s and a discard at 6.5 s.  Outside
    the window: a check at 11-12 s.  ``bench:call`` spans enclose each."""
    s = 1e9
    return [("bench:call", 0.5 * s, 5 * s), ("bench:call", 5.5 * s, 4 * s),
            ("bench:drivers.check", 1 * s, 4 * s),
            ("bench:prepare", 1 * s, 1 * s),
            ("bench:drivers.poll", 2 * s, 1 * s),
            ("bench:drivers.poll", 3.5 * s, 1 * s),
            ("bench:drivers.check", 6 * s, 3 * s),
            ("bench:drivers.poll", 7 * s, 2 * s),
            ("bench:drivers.discard", 6.5 * s, 100.0),
            ("bench:drivers.check", 11 * s, 1 * s)]


@pytest.mark.parametrize("span_name,what,within,want", [
    ("drivers.check", "s_per_call", None, 3.5),
    ("drivers.check", "self_s_per_call", None, 1.0),
    ("drivers.poll", "share", None, 40.0),
    ("drivers.poll", "count_per_call", None, 1.5),
    ("drivers.poll", "s_per_span", None, 4.0 / 3),
    ("prepare", "s_per_call", None, 0.5),
    ("drivers.discard", "count_per_call", "drivers.check", 0.5),
    # none of that name, but the program has spans: a count of zero
    ("drivers.grow", "count_per_call", "drivers.check", 0.0),
    # a program without spans (the parent commit): nothing to read
    ("drivers.grow", "count_per_call", "no.such", None),
    ("witness.cpu", "s_per_span", None, None),
])
def test_program_span_reductions(program_span, span_name, what, within,
                                 want):
    got = program_span.reduce(_spans(), 0.0, 10e9, 2, span_name, what,
                              within)
    assert got == (pytest.approx(want, abs=1e-6) if want is not None
                   else None)


def test_program_span_clips_to_the_window_and_reads_ctx(program_span):
    class Trace:
        spans, t0, t1 = _spans(), 2.5e9, 8e9
    ctx = {"trace": Trace, "counters": {"calls": 2}}
    # check 2.5-5 and 6-8; polls 2.5-3, 3.5-4.5, 7-8
    assert program_span.read(ctx, "drivers.check", "s_per_call") == \
        pytest.approx(2.25)
    assert program_span.read(ctx, "drivers.poll", "share") == \
        pytest.approx(100 * 2.5 / 5.5)
    # a span that began before the window is not one of its starts
    assert program_span.read(ctx, "drivers.check", "count_per_call") == 0.5
    assert program_span.read({"trace": None, "counters": {}},
                             "drivers.check", "share") is None
    with pytest.raises(ValueError):
        program_span.read(ctx, "drivers.check", "median")


# -- device scopes ------------------------------------------------------------

@pytest.mark.parametrize("variant,kw,absent", [
    ("multi-round", {"gwords": 1}, {"wgl.gather", "wgl.gather_shards"}),
    ("single-round", {"gwords": 1, "work_budget": 0,
                      "single_round_closure": True,
                      "steps_per_dispatch": 8}, {"wgl.gather_shards"}),
])
def test_engine_scopes_in_lowered_text(variant, kw, absent):
    """The lowered engine names each phase; the program itself (the text
    without locations) says nothing of them: scopes are metadata."""
    carry0, _, run_chunk = wgl_tpu.make_engine(
        get_model("cas-register"), 8, 16, **kw)
    low = jax.jit(run_chunk).lower(carry0(), jnp.zeros((8, 10), jnp.int32))
    with_locations = low.as_text(debug_info=True)
    for scope in wgl_tpu.ENGINE_SCOPES:
        assert (scope in with_locations) == (scope not in absent), scope
    assert "wgl." not in low.as_text()


@pytest.mark.parametrize("realtime", [True, False])
def test_elle_kernel_scopes_in_lowered_text(realtime):
    """The closure kernel names its four phases, with the realtime layer
    and without; the program's text is the same without them."""
    from jepsen_tpu.elle_tpu import closure
    fn = closure.lane_flags_fn(32, realtime)
    edges = jnp.full((1, 3, 64), -1, jnp.int32)
    times = jnp.zeros((1, 32), jnp.int32)
    low = fn.lower(edges, edges, times, times)
    with_locations = low.as_text(debug_info=True)
    assert closure.KERNEL_SCOPES == ("elle.layers", "elle.realtime",
                                     "elle.closure", "elle.flags")
    for scope in closure.KERNEL_SCOPES:
        assert scope in with_locations, scope
    assert "elle." not in low.as_text()
