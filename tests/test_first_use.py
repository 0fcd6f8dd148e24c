"""First-use counters (jepsen_tpu.obs.hist.first_use_stats): what JAX
traces, lowers and compiles once a process, by phase and by who asked, and
``core.analyze``'s first call against its later ones; the recorder events
they leave; and the two reductions that read them: the benchmark's
``program_sums`` reader and ``scripts/warmup_trace.py``'s span table, each
on hand-made input.
"""

import importlib.util
import os
import sys

import jax
import jax.monitoring
import jax.numpy as jnp
import pytest

from jepsen_tpu import core
from jepsen_tpu.checker import wgl_tpu
from jepsen_tpu.models import get_model
from jepsen_tpu.obs import hist
from jepsen_tpu.obs.recorder import span
from jepsen_tpu.synth import cas_register_history

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRACE = "/jax/core/compile/jaxpr_trace_duration"
PHASES = ("trace_s", "lower_s", "load_s")


@pytest.fixture
def first_use():
    """The process's sums, from zero and listening; the engine cache and
    JAX's own caches are whatever earlier tests left."""
    hist.listen_first_use()
    hist.reset_first_use_stats()
    yield hist.first_use_stats
    hist.reset_first_use_stats()


def by_path(name, *parts):
    """A module of the repo that is no package's, imported by its path."""
    spec = importlib.util.spec_from_file_location(
        name + "_under_test", os.path.join(ROOT, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def history(seed=3):
    return cas_register_history(120, concurrency=4, crash_p=0.01, seed=seed)


def test_the_keys_are_a_contract(first_use):
    """docs/observability.md and the benchmark's six ``layers/*.json`` name
    these keys."""
    assert set(first_use()) == set(hist.FIRST_USE_KEYS) == {
        "trace_s", "lower_s", "load_s", "programs", "cache_hits",
        "cache_misses", "retrieval_s", "engine_s", "eager_s",
        "eager_programs", "by_shape", "eager_by_fun", "first_calls",
        "first_call_s", "analyze_calls", "analyze_first_s",
        "analyze_later_s"}
    assert all(not v for v in first_use().values())


def test_listening_twice_registers_once(first_use):
    hist.listen_first_use()
    hist.listen_first_use()

    @jax.jit
    def heard_once(x):
        return x + 1
    x = jnp.ones(2)
    hist.reset_first_use_stats()
    heard_once(x)
    assert first_use()["programs"] == 1


def test_a_nested_trace_is_counted_once(first_use):
    """A jitted function that calls jitted functions fires one trace event
    for each (481 events for 21 programs in a warm-up call): the seconds
    are the outermost event's, not the sum."""
    raw = []

    def listen(event, secs, **kw):
        if event == TRACE:
            raw.append((kw.get("fun_name"), secs))

    @jax.jit
    def inner(x):
        return x * 2 + 1

    @jax.jit
    def outer(x):
        return inner(x) + inner(x[:2]).sum()

    x = jnp.arange(4.0)
    hist.reset_first_use_stats()        # arange's own program is not ours
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        y = outer(x)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    got = first_use()
    names = [name for name, _ in raw]
    assert names.count("outer") == 1 and names.count("inner") == 2
    assert names[-1] == "outer"         # the outermost closes last
    assert got["trace_s"] == pytest.approx(raw[-1][1], abs=1e-9)
    assert got["trace_s"] < sum(s for _, s in raw)
    # one program, under the name of the function that was called
    assert list(got["eager_by_fun"]) == ["outer"]
    assert got["eager_by_fun"]["outer"]["programs"] == got["programs"] == 1
    assert got["eager_by_fun"]["outer"]["s"] == pytest.approx(
        sum(got[k] for k in PHASES))
    assert float(y[0]) == 5.0


def test_an_engine_lands_in_by_shape_and_an_eager_op_by_its_name(first_use):
    """The engine cache keys on the chunk, ``COMPILES`` does not: an odd
    chunk is a first call under a key other tests share."""
    before = hist.compile_hist_stats()
    res = wgl_tpu.check(get_model("cas-register"), history(), chunk=40)
    assert res["valid"] is True
    got = first_use()
    shape, = got["by_shape"]
    assert shape.startswith("compile:singlev:cas-register:")
    grew = hist.compile_hist_stats()[shape]["count"] \
        - before.get(shape, {"count": 0})["count"]
    assert grew == got["first_calls"] == 1
    mine = got["by_shape"][shape]
    assert set(mine) == {"trace_s", "lower_s", "load_s", "first_call_s",
                         "cache"}
    assert all(mine[k] > 0 for k in PHASES)
    assert mine["cache"] is None        # no persistent cache on the CPU
    assert mine["first_call_s"] == got["first_call_s"] \
        >= sum(mine[k] for k in PHASES) == pytest.approx(got["engine_s"])
    assert got["programs"] == got["eager_programs"] + 1
    # the books: every second is an engine's or an eager program's
    assert sum(got[k] for k in PHASES) == pytest.approx(
        got["engine_s"] + got["eager_s"], abs=1e-9)
    assert got["eager_s"] == pytest.approx(
        sum(f["s"] for f in got["eager_by_fun"].values()), abs=1e-9)
    assert got["eager_programs"] == sum(
        f["programs"] for f in got["eager_by_fun"].values())

    # an eager op outside any engine, under its own name
    @jax.jit
    def lonely_op(x):
        return x - 3
    lonely_op(jnp.zeros(3))
    after = first_use()
    assert after["eager_by_fun"]["lonely_op"]["programs"] == 1
    assert after["eager_by_fun"]["lonely_op"]["s"] > 0
    assert after["by_shape"] == got["by_shape"]
    assert after["engine_s"] == got["engine_s"]
    assert after["eager_s"] > got["eager_s"]


def test_a_second_check_of_the_same_shapes_moves_nothing(first_use):
    model, h = get_model("cas-register"), history()
    wgl_tpu.check(model, h)
    first = first_use()
    assert wgl_tpu.check(model, h)["valid"] is True
    assert first_use() == first


def test_analyze_first_against_later(first_use, monkeypatch):
    clock = iter([0.0, 5.0, 10.0, 11.0, 20.0, 22.5])
    monkeypatch.setattr(core, "mono_now", lambda: next(clock))
    for _ in range(3):
        assert core.analyze({}, history())["valid"] is True
    got = first_use()
    assert (got["analyze_calls"], got["analyze_first_s"],
            got["analyze_later_s"]) == (3, 5.0, 3.5)


def test_analyze_is_timed_when_the_checker_raises(first_use):
    with pytest.raises(KeyError):
        core.analyze({"checker": "no-such-checker"}, history())
    assert first_use()["analyze_calls"] == 1


@pytest.mark.parametrize("on", [True, False])
def test_recorder_events_sit_inside_the_open_span(first_use, rec, on):
    rec.enabled = on

    @jax.jit
    def fresh(x):
        return x * x

    run = hist.timed_first_call(fresh, "compile:test:first_use")
    with span("drivers.rung") as rung:
        run(jnp.ones(5))
    evs = rec.snapshot()
    got = first_use()
    # recorder on or off, the counters count
    assert got["by_shape"]["compile:test:first_use"]["load_s"] > 0
    assert got["first_calls"] == 1
    if not on:
        assert evs == []
        return
    ev = {e["name"]: e for e in evs}
    assert set(ev) == {"drivers.rung", "compile.first_call",
                       "compile.trace", "compile.lower", "compile.load"}
    first = ev["compile.first_call"]
    assert first["parent-span-id"] == ev["drivers.rung"]["span-id"]
    for phase in ("trace", "lower", "load"):
        e = ev["compile." + phase]
        assert e["parent-span-id"] == first["span-id"]
        assert e["trace-id"] == first["trace-id"] and "span-id" not in e
        assert e["args"]["fun"] == "fresh"
        assert e["args"]["shape"] == "compile:test:first_use"
        # placed by its duration: inside the span that was open
        assert first["ts"] - 1e-3 <= e["ts"]
        assert e["ts"] + e["dur-s"] <= first["ts"] + first["dur-s"] + 1e-3
        assert e["dur-s"] == pytest.approx(
            got["by_shape"]["compile:test:first_use"][phase + "_s"])
    assert ev["compile.load"]["args"]["cache"] is None
    assert "cache" not in ev["compile.trace"]["args"]
    assert ev["compile.trace"]["ts"] <= ev["compile.lower"]["ts"] \
        <= ev["compile.load"]["ts"]
    assert rung.dur_s >= first["dur-s"]


def test_an_eager_program_is_an_event_with_no_shape(first_use, rec):
    @jax.jit
    def eager_one(x):
        return x + 7

    with span("drivers.stage"):
        eager_one(jnp.ones(2))
    ev = {e["name"]: e for e in rec.snapshot()
          if e.get("args", {}).get("fun") == "eager_one"}
    assert set(ev) == {"compile.trace", "compile.lower", "compile.load"}
    stage, = (e for e in rec.snapshot() if e["name"] == "drivers.stage")
    for e in ev.values():
        assert e["args"]["shape"] is None
        assert e["parent-span-id"] == stage["span-id"]


# -- the benchmark's reader: sums less sums, on a hand-made dict --------------

@pytest.fixture(scope="module")
def program_sums():
    return by_path("program_sums", "benchmark", "readers", "program_sums.py")


LATER = {"sum": "analyze_later_s", "over": "analyze_calls", "less": 1}
SUMS = {"analyze_calls": 4, "analyze_first_s": 9.0, "analyze_later_s": 6.0,
        "trace_s": 1.0, "lower_s": 0.5, "load_s": 1.5, "eager_s": 0.25}


@pytest.mark.parametrize("sums,plus,minus,want", [
    (SUMS, ["trace_s"], [], 1.0),
    (SUMS, ["trace_s", "lower_s", "load_s"], ["eager_s"], 2.75),
    # the warm-up's excess: the first call less the mean of the later ones
    (SUMS, ["analyze_first_s"], [LATER], 7.0),
    (SUMS, ["analyze_first_s"], [LATER, "trace_s", "lower_s", "load_s"],
     4.0),
    # a key the program does not have, on either side: nothing to read
    (SUMS, ["no_such_s"], [], None),
    (SUMS, ["trace_s"], ["no_such_s"], None),
    (SUMS, ["analyze_first_s"],
     [{"sum": "no_such_s", "over": "analyze_calls", "less": 1}], None),
    # one call only: no later call to take the mean of
    (dict(SUMS, analyze_calls=1, analyze_later_s=0.0),
     ["analyze_first_s"], [LATER], None),
    (dict(SUMS, analyze_calls=0), ["analyze_first_s"], [LATER], None),
])
def test_program_sums_reductions(program_sums, sums, plus, minus, want):
    got = program_sums.reduce(sums, plus, minus)
    assert got == (pytest.approx(want) if want is not None else None)


def test_program_sums_reads_the_program_or_nothing(program_sums, first_use):
    read = program_sums.read
    core.analyze({}, history())
    core.analyze({}, history())
    stats = "jepsen_tpu.obs.hist:first_use_stats"
    got = first_use()
    assert read({}, stats, ["analyze_first_s"], [LATER]) == pytest.approx(
        got["analyze_first_s"] - got["analyze_later_s"])
    assert read({}, stats, ["trace_s"]) == got["trace_s"]
    # a parent commit without the function, or without the module
    assert read({}, "jepsen_tpu.obs.hist:no_such_stats", ["trace_s"]) is None
    assert read({}, "jepsen_tpu.obs.no_such:first_use_stats",
                ["trace_s"]) is None


@pytest.mark.parametrize("name", [
    "setup.warmup_excess_s", "compile.trace_s", "compile.lower_s",
    "compile.load_s", "compile.eager_s", "setup.warmup_unnamed_s"])
def test_the_six_layer_files_read_keys_the_program_has(program_sums, name):
    import json
    with open(os.path.join(ROOT, "benchmark", "layers", name + ".json")) as f:
        spec = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    entry, = (m for m in man["per_layer"] if m["name"] == name)
    assert {k: spec[k] for k in ("layer", "unit", "moves")} == {
        k: entry[k] for k in ("layer", "unit", "moves")} == {
        "layer": "compile", "unit": "s", "moves": "setup_s"}
    assert entry["workloads"] == [w["name"] for w in man["workloads"]]
    assert spec["reader"] == "program_sums"
    module, attr = spec["args"]["stats"].split(":")
    assert getattr(sys.modules[module], attr) is hist.first_use_stats
    terms = spec["args"]["plus"] + spec["args"].get("minus", [])
    keys = {k for t in terms
            for k in ([t] if isinstance(t, str) else [t["sum"], t["over"]])}
    assert keys <= set(hist.FIRST_USE_KEYS)
    sums = dict(hist._zero_first_use(), **SUMS)
    assert program_sums.reduce(sums, spec["args"]["plus"],
                               spec["args"].get("minus", ())) is not None


# -- scripts/warmup_trace.py: call 1 less call 2, by span ---------------------

@pytest.fixture(scope="module")
def warmup_trace():
    return by_path("warmup_trace", "scripts", "warmup_trace.py")


def _call(rung_s, poll_s, compile_s=None):
    """One call's recorder events: analyze > check > rung > {dispatch >
    first_call > compile.load, poll}, and an instant."""
    evs = [{"name": "entry.analyze", "span-id": "a", "dur-s": rung_s + 0.3},
           {"name": "drivers.check", "span-id": "c", "parent-span-id": "a",
            "dur-s": rung_s + 0.2},
           {"name": "drivers.rung", "span-id": "r", "parent-span-id": "c",
            "dur-s": rung_s},
           {"name": "drivers.poll", "span-id": "p1", "parent-span-id": "r",
            "dur-s": poll_s},
           {"name": "drivers.poll", "span-id": "p2", "parent-span-id": "r",
            "dur-s": poll_s},
           {"name": "drivers.discard", "parent-span-id": "r"}]
    if compile_s is not None:
        evs += [{"name": "drivers.dispatch", "span-id": "d",
                 "parent-span-id": "r", "dur-s": compile_s + 0.5},
                {"name": "compile.first_call", "span-id": "f",
                 "parent-span-id": "d", "dur-s": compile_s + 0.25},
                {"name": "compile.load", "parent-span-id": "f",
                 "dur-s": compile_s}]
    return evs


def test_warmup_trace_excess_by_span(warmup_trace):
    first, later = _call(8.0, 1.0, compile_s=3.0), _call(2.5, 1.0)
    assert warmup_trace.by_span(first)["drivers.rung"] == [
        1, 8.0, pytest.approx(8.0 - 2 * 1.0 - 3.5)]
    rows = warmup_trace.excess_by_span(first, later)
    assert list(rows)[:2] == ["compile.load", "drivers.rung"]
    assert rows["compile.load"] == {
        "count": [1, 0], "s": [3.0, 0.0], "self_s": [3.0, 0.0],
        "excess_s": 3.0, "excess_self_s": 3.0}
    rung = rows["drivers.rung"]
    assert rung["excess_s"] == 5.5
    assert rung["excess_self_s"] == pytest.approx(2.5 - 0.5)
    assert rows["drivers.poll"]["count"] == [2, 2]
    assert rows["drivers.poll"]["excess_self_s"] == 0.0
    assert rows["compile.first_call"]["self_s"] == [0.25, 0.0]
    assert rows["drivers.discard"]["s"] == [0.0, 0.0]
    # the self times of a call add up to its wall: so do the excesses
    assert sum(r["excess_self_s"] for r in rows.values()) == pytest.approx(
        first[0]["dur-s"] - later[0]["dur-s"])


def test_warmup_trace_reports_what_a_second_call_moved(warmup_trace):
    before = dict(hist._zero_first_use(), analyze_calls=1,
                  analyze_first_s=8.0, trace_s=1.0,
                  by_shape={"s": {"load_s": 1.0}},
                  eager_by_fun={"iota": {"programs": 1, "s": 0.1}})
    after = dict(before, analyze_calls=2, analyze_later_s=2.0)
    assert warmup_trace.moved(before, after) == {
        "analyze_calls": 1, "analyze_later_s": 2.0}
    late = dict(after, trace_s=1.5, eager_by_fun={
        "iota": {"programs": 1, "s": 0.1},
        "tile": {"programs": 1, "s": 0.2}})
    assert warmup_trace.moved(after, late) == {
        "trace_s": 0.5, "eager_by_fun": {"tile": {"programs": 1, "s": 0.2}}}
