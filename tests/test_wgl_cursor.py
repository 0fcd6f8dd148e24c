"""The single-history driver's event cursor (``wgl_tpu._check``): the
position in the stream is a device scalar that travels with the carry, so
a chunk dispatched behind a budget pause goes on from the pause.  CPU
backend: counts and verdicts only, no times."""

import jax
import numpy as np
import pytest

from jepsen_tpu.checker import wgl_cpu, wgl_tpu
from jepsen_tpu.checker.prep import prepare
from jepsen_tpu.history import History
from jepsen_tpu.models import CASRegister, get_model
from jepsen_tpu.synth import cas_register_history, corrupt_reads

CHUNK = 64
#: ``TestClosureWorkBudget``'s set-up (tests/test_wgl_tpu.py): 16 closure
#: rounds a dispatch at any capacity, so nearly every dispatch pauses
TINY_BUDGET = 64
#: a capacity the histories below never outgrow
ROOMY = 1024


@pytest.fixture
def model():
    return get_model("cas-register")


@pytest.fixture
def tiny_budget(monkeypatch):
    monkeypatch.setattr(wgl_tpu, "CLOSURE_WORK_BUDGET", TINY_BUDGET)


def nop_stream(rows=4 * CHUNK):
    ev = np.zeros((rows, 10), np.int32)
    ev[:, 0] = wgl_tpu.EV_NOP
    return ev


def history(seed, refuted=False):
    h = cas_register_history(300, concurrency=6, crash_p=0.01, seed=seed)
    return corrupt_reads(h, n=1, seed=seed) if refuted else h


def traced_check(rec, model, h, **kw):
    """``(result, what drivers.check closed with, polls' args, discards'
    args, stage's args)`` of one ``wgl_tpu.check``."""
    rec.clear()
    res = wgl_tpu.check(model, h, explain=False,
                        **{"chunk": CHUNK, **kw})
    evs = rec.snapshot()

    def args(name):
        return [e["args"] for e in evs if e["name"] == name]

    (did,), (stage,) = args("drivers.check"), args("drivers.stage")
    return res, did, args("drivers.poll"), args("drivers.discard"), stage


def search_counts(res):
    """What the search did, as far as the result says (a refutation has no
    ``closure-rounds``)."""
    return {k: res.get(k) for k in ("valid", "configs-explored",
                                    "closure-rounds")} \
        | {"op": (res.get("op") or {}).get("index")}


class TestPausesCostNoDispatch:
    def test_no_chunk_is_discarded_for_a_pause(self, rec, model,
                                               tiny_budget, monkeypatch):
        started_at = []  # the device's cursor at each dispatch
        get = wgl_tpu._get_run_chunk

        def spying(*a):
            carry0, run = get(*a)

            def run_and_tell(carry, cursor, ev_dev):
                started_at.append(int(cursor))
                return run(carry, cursor, ev_dev)
            return carry0, run_and_tell

        monkeypatch.setattr(wgl_tpu, "_get_run_chunk", spying)
        res, did, polls, discards, _ = traced_check(
            rec, model, history(3), capacity=ROOMY)
        # what the host reckons at the poll is where the device had begun
        assert started_at == [p["pos"] for p in polls]
        assert res["valid"] is True
        assert did["resumes"] >= 10
        assert did["discarded"] == 0 and discards == []
        assert did["continued"] >= did["resumes"] - 1
        # every poll is an accepted one: a dispatch each, and each went on
        # where the one before it stopped
        assert did["dispatches"] == len(polls)
        assert did["resumes"] == sum(p["consumed"] < CHUNK for p in polls)
        pos = 0
        for p in polls:
            assert p["pos"] == pos
            pos += p["consumed"]
        assert pos == did["events_consumed"]

    def test_a_grow_still_discards_but_never_for_a_resume(self, rec, model,
                                                          tiny_budget):
        res, did, _, discards, _ = traced_check(
            rec, model, history(3), capacity=16)
        assert res["valid"] is True and did["grows"] >= 1
        assert did["discarded"] == len(discards) >= 1
        assert {d["why"] for d in discards} == {"grow"}

    @pytest.mark.parametrize("refuted", [False, True],
                             ids=["valid", "refuted"])
    @pytest.mark.parametrize("seed", [3, 5, 7])
    def test_same_search_as_the_oracle_and_as_lookahead_1(
            self, rec, model, tiny_budget, monkeypatch, seed, refuted):
        h = history(seed, refuted)
        res, did, _, _, _ = traced_check(rec, model, h, capacity=ROOMY)
        oracle = wgl_cpu.check(CASRegister(), h)
        assert res["valid"] is oracle["valid"] is (not refuted)
        if refuted:
            assert res["op"]["index"] == oracle["op"]["index"]
        else:
            assert did["resumes"] >= 10
        monkeypatch.setattr(wgl_tpu, "LOOKAHEAD", 1)
        res1, did1, _, _, _ = traced_check(rec, model, h, capacity=ROOMY)
        assert search_counts(res) == search_counts(res1)
        # nothing speculated, so nothing could continue: the same polls
        for k in ("resumes", "events_consumed", "cap_events",
                  "peak_events"):
            assert did[k] == did1[k]
        assert did1["discarded"] == 0
        # the one chunk a refutation leaves in flight is the only extra
        assert did["dispatches"] - did["discarded"] == did1["dispatches"]


class TestCapacityChangesKeepTheCursor:
    @pytest.mark.parametrize("seed", [3, 5])
    def test_grow_in_a_paused_stretch_resumes_at_the_snapshot(
            self, rec, model, tiny_budget, seed):
        """No event applied twice or skipped: the run that climbs from 16
        counts what the run started at the capacity it reached counts."""
        h = history(seed)
        res, did, polls, _, _ = traced_check(rec, model, h, capacity=16)
        assert did["grows"] >= 2
        # a grow whose overflowing chunk had been dispatched behind a pause
        over = [i for i, p in enumerate(polls) if p["overflow"]]
        assert any(i and polls[i - 1]["consumed"] < CHUNK for i in over)
        # the re-dispatch starts where the overflowing chunk had started
        for i in over:
            assert polls[i + 1]["pos"] == polls[i]["pos"]
        top = res["max-capacity-reached"]
        res_top, did_top, polls_top, _, _ = traced_check(
            rec, model, h, capacity=top)
        assert did_top["grows"] == 0
        assert search_counts(res) == search_counts(res_top)
        accepted = [(p["pos"], p["consumed"]) for p in polls
                    if not p["overflow"]]
        assert accepted == [(p["pos"], p["consumed"]) for p in polls_top]
        assert did["events_consumed"] == did_top["events_consumed"]

    def test_shrink_resumes_at_the_polled_chunks_end(self, rec, model,
                                                     tiny_budget):
        """A concurrent burst, then a calm tail: the driver climbs to 256,
        shrinks back, and counts what a run that never left 256 counts."""
        burst = cas_register_history(60, concurrency=8, crash_p=0.0, seed=4)
        calm = cas_register_history(200, concurrency=2, crash_p=0.0, seed=11)
        h = History(list(burst) + list(calm), reindex=True)
        res, did, polls, discards, _ = traced_check(
            rec, model, h, capacity=16, chunk=16)
        assert res["valid"] is True
        assert did["grows"] >= 1 and did["shrinks"] >= 1 \
            and did["resumes"] >= 1
        assert {d["why"] for d in discards} <= {"grow", "shrink"}
        # each poll starts where the accepted ones before it had got to
        pos = 0
        for p in polls:
            assert p["pos"] == pos
            pos += 0 if p["overflow"] else p["consumed"]
        res_top, did_top, _, _, _ = traced_check(
            rec, model, h, capacity=res["max-capacity-reached"], chunk=16)
        assert did_top["grows"] == did_top["shrinks"] == 0
        assert search_counts(res) == search_counts(res_top)
        assert did["events_consumed"] == did_top["events_consumed"]


class TestTheStreamsEnd:
    def test_final_events_pause_and_the_stream_is_covered(self, rec, model,
                                                          tiny_budget):
        h = history(3)
        res, did, polls, _, stage = traced_check(rec, model, h,
                                                 capacity=ROOMY)
        assert res["valid"] is True
        p = prepare(h, model)
        n_events = -(-len(p) // CHUNK) * CHUNK
        # the chunk that holds the last real event was dispatched behind
        # a pause
        last = max(i for i, q in enumerate(polls) if q["pos"] < len(p))
        assert polls[last - 1]["consumed"] < CHUNK
        # every chunk starts before the padded stream's end, so its slice
        # ends inside the cushion; the stream is consumed whole, and the
        # last chunk runs on into the cushion's NOPs by less than a chunk
        rows = stage["bytes"] // (10 * 4)
        for q in polls:
            assert q["pos"] < n_events and q["pos"] + CHUNK <= rows
        assert n_events <= did["events_consumed"] < n_events + CHUNK
        assert did["events_consumed"] == polls[-1]["pos"] \
            + polls[-1]["consumed"]

    @pytest.mark.parametrize("n_ops", [20, 150, 300])
    def test_staged_rows_are_a_rung_of_the_event_ladder(self, rec, model,
                                                        n_ops):
        """The runner slices the staged stream, so its length is part of
        the compiled shape: a power of two with room for the cushion."""
        h = cas_register_history(n_ops, concurrency=4, crash_p=0.0, seed=1)
        _, did, _, _, stage = traced_check(rec, model, h, capacity=64)
        rows = stage["bytes"] // (10 * 4)
        n_events = max(1, -(-did["events"] // CHUNK)) * CHUNK
        assert rows & (rows - 1) == 0
        assert n_events + CHUNK <= rows < 2 * (n_events + CHUNK)


class TestOneDispatchIsOneLaunch:
    def test_the_runners_program_holds_the_slice(self, model):
        window, cap, gw = 8, 64, 1
        carry0, run = wgl_tpu._get_run_chunk(model, window, cap, gw, CHUNK)
        assert wgl_tpu._get_run_chunk(model, window, cap, gw, CHUNK)[1] \
            is run
        assert wgl_tpu._get_run_chunk(model, window, cap, gw,
                                      2 * CHUNK)[1] is not run
        jaxpr = jax.make_jaxpr(run)(carry0(), np.int32(0),
                                    nop_stream()).jaxpr
        # one jitted program a dispatch ...
        assert [e.primitive.name for e in jaxpr.eqns] in (["jit"], ["pjit"])
        inner = [e.primitive.name
                 for e in jaxpr.eqns[0].params["jaxpr"].jaxpr.eqns]
        # ... which cuts its own chunk (behind the barrier that keeps the
        # TPU compiler's prefetches: _get_run_chunk) and scans it
        assert inner.index("optimization_barrier") \
            < inner.index("dynamic_slice") < inner.index("scan")

    def test_the_cursor_comes_back_advanced_by_consumed(self, model):
        carry0, run = wgl_tpu._get_run_chunk(model, 8, 64, 1, CHUNK)
        ev = nop_stream()
        carry, cursor, flags = run(carry0(), np.int32(5), ev)
        assert int(flags[3]) == CHUNK and int(cursor) == 5 + CHUNK
        _, cursor, flags = run(carry, cursor, ev)
        assert int(flags[3]) == CHUNK and int(cursor) == 5 + 2 * CHUNK
