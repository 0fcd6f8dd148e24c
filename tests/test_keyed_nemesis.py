"""The ``keyed-register-nemesis`` deployment at a small size on the CPU: the
benchmark's generator (``benchmark/gen/nemesis_keyed.py``) holds the
source's shapes; ``independent.checker(linearizable(cas-register))`` agrees
with the benchmark's plain reference and with the host oracle key for key
and refuting op for refuting op; ``batch_stats()`` and the batch driver's
spans count what the passes did; the ``offline_plug`` loop finds a generator
by its module; and the register workload stays what the suites run.
"""

import json
import os
import random
import sys
import types
from collections import Counter

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from gen import histories as H  # noqa: E402
from gen import nemesis_keyed as N  # noqa: E402
from reference import wgl_register  # noqa: E402

from jepsen_tpu import core, generator as gen, independent  # noqa: E402
from jepsen_tpu.checker import wgl_cpu  # noqa: E402
from jepsen_tpu.checker.linearizable import linearizable  # noqa: E402
from jepsen_tpu.generator import testkit  # noqa: E402
from jepsen_tpu.history import History, Op  # noqa: E402
from jepsen_tpu.models import get_model  # noqa: E402
from jepsen_tpu.obs.recorder import RECORDER  # noqa: E402
from jepsen_tpu.checker.prep import prepare  # noqa: E402
from jepsen_tpu.parallel import batch, batch_stats, check_batch  # noqa: E402
from jepsen_tpu.synth import cas_register_history  # noqa: E402
from jepsen_tpu.workloads import linearizable_register  # noqa: E402

THREADS, READERS, PROCESS_LIMIT = 10, 5, 20


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def small():
    """The cell's own files, cut to 24 keys of at most 60 ops; blocks of 4
    keys alternate healed and partitioned, and a timeout lasts 2 ops so
    that a 60-op key sees several."""
    config = load("configs", "keyed-register-nemesis")
    params = load("traffic", "offline-keyed-nemesis")["params"]
    config.update(keys=24, per_key_limit=60)
    params.update(partition_block=4, refute_every=8, timeout_ops=2)
    return config, params


def one_key(seed, cut_off=(), n_ops=60, crash_p=0.005, timeout_ops=2):
    return N.register_history(n_ops, THREADS, READERS, 5, crash_p, 0.5,
                              PROCESS_LIMIT, list(cut_off), timeout_ops,
                              random.Random(seed))


def threads_of(history):
    """process -> thread, replayed: a thread starts as the process of its
    own number, and each ``info`` hands its thread the next fresh id."""
    thread = {p: p for p in range(THREADS)}
    fresh = THREADS
    for o in history:
        if o.type == H.INFO:
            thread[fresh] = thread[o.process]
            fresh += 1
    return thread


def completions(history):
    """(invoke, completion) pairs in invoke order."""
    open_at, out = {}, []
    for o in history:
        if o.type == H.INVOKE:
            open_at[o.process] = o
        else:
            out.append((open_at.pop(o.process), o))
    assert not open_at
    return out


# -- the generator's invariants -------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 100, 2026])
@pytest.mark.parametrize("cut_off", [(), (1, 3, 6, 8), (0, 4, 5, 9)])
def test_a_key_has_ten_threads_and_readers_never_write(seed, cut_off):
    h = one_key(seed, cut_off)
    thread = threads_of(h)
    busy, peak = set(), 0
    for o in h:
        t = thread[o.process]
        if o.type == H.INVOKE:
            assert t not in busy, "a thread has one op in flight"
            busy.add(t)
            assert (o.f == "read") == (t < READERS)
        else:
            busy.remove(t)
        peak = max(peak, len(busy))
    assert peak <= THREADS and set(thread.values()) == set(range(THREADS))
    fs = Counter(inv.f for inv, _ in completions(h))
    assert fs["cas"] > fs["write"] > 0 and fs["read"] > 0
    assert 1 <= len(completions(h)) <= 60
    assert len({o.process for o in h}) <= PROCESS_LIMIT
    assert wgl_register.check(h)["valid"] is True


@pytest.mark.parametrize("seed", [1, 5, 11])
def test_minority_reads_fail_and_minority_writes_crash(seed):
    cut_off = (1, 3, 6, 8)
    h = one_key(seed, cut_off, n_ops=120)
    thread = threads_of(h)
    timed_out = 0
    for inv, comp in completions(h):
        if thread[inv.process] in cut_off:
            timed_out += 1
            assert comp.error == "timeout"
            assert comp.type == (H.FAIL if inv.f == "read" else H.INFO)
        else:
            assert comp.error in (None, "crashed")
            assert comp.type != H.INFO or comp.error == "crashed"
    assert timed_out >= 8
    # a timeout is long: the other threads did ~timeout_ops ops each meanwhile
    assert timed_out < len(completions(h)) / 3


@pytest.mark.parametrize("seed", range(6))
def test_process_limit_cuts_a_key_at_the_crash_that_needs_a_21st(seed):
    """Crash-heavy, so that the limit bites: no op is invoked after the
    11th ``info`` (10 threads + 10 fresh processes = 20), the ops in flight
    still complete, and an uncut key runs to its own limit."""
    h = one_key(seed, (0, 2, 5, 7), n_ops=400, crash_p=0.05, timeout_ops=1)
    infos = [i for i, o in enumerate(h) if o.type == H.INFO]
    assert len(infos) >= PROCESS_LIMIT - THREADS + 1, "not crash-heavy enough"
    cut_at = infos[PROCESS_LIMIT - THREADS]
    assert not any(o.type == H.INVOKE for o in h[cut_at:])
    assert len(completions(h)) < 400
    # fresh processes up to the 20th exist; the last few may not have got
    # an op in before the cut
    assert THREADS + 5 < len({o.process for o in h}) <= PROCESS_LIMIT
    assert wgl_register.check(h)["valid"] is True
    calm = one_key(seed, (), n_ops=60, crash_p=0.0)
    assert len(completions(calm)) == 60
    assert not any(o.type == H.INFO for o in calm)


def shapes(gen_out):
    return sorted([(o.type, o.f) for o in recs]
                  for recs in H.split_keys(gen_out["records"]).values())


def test_every_seed_is_the_same_structure(capsys):
    config, params = small()
    a = N.keyed_nemesis(config, params, 3)
    b = N.keyed_nemesis(config, params, 2**31 + 17)
    assert a == N.keyed_nemesis(config, params, 3)
    assert a["records"] != b["records"] and a["keyed"] is True
    assert shapes(a) == shapes(b)
    lanes = H.split_keys(a["records"])
    assert len(lanes) == 24
    # the jitter: a key's limit is 0.9 to 1.0 of per_key_limit
    assert {len(completions(v)) for v in lanes.values()} <= set(range(54, 61))
    assert len({len(completions(v)) for v in lanes.values()}) > 1
    # 12 keys lived in a partition: timeouts there and nowhere else
    timed = [k for k, v in lanes.items()
             if any(o.error == "timeout" for o in v)]
    assert len(timed) == 12
    # one refuted lane in 8, healed and partitioned both
    bad = [k for k, v in lanes.items()
           if any(isinstance(o.value, int) and o.value >= 1000 for o in v)]
    assert len(bad) == 3 and 0 < len(set(bad) & set(timed)) < 3
    for k, v in lanes.items():
        assert {o.process for o in v} <= set(
            range(k * params["process_stride"],
                  (k + 1) * params["process_stride"]))
    said = capsys.readouterr().out
    assert "info ops per key" in said and "peak pending per key" in said
    assert "keys cut by process_limit 20" in said


def test_lane_stats_is_the_checkers_window():
    from jepsen_tpu.checker.prep import prepare
    model = get_model("cas-register")
    for seed in range(5):
        h = one_key(seed, (1, 3, 6, 8), n_ops=80)
        ops, infos, peak, procs = N.lane_stats(h)
        p = prepare(History([Op(process=r.process, type=r.type, f=r.f,
                                value=r.value, time=r.time) for r in h],
                            reindex=True), model)
        assert peak == p.window
        assert ops == len(completions(h))
        assert infos == sum(o.type == H.INFO for o in h)
        assert THREADS <= procs <= THREADS + infos


# -- the system against the plain reference, key for key ----------------------

@pytest.fixture(scope="module")
def analyzed():
    """One ``core.analyze`` of the small keyed history with the recorder
    on, and what it added to the batch counters: shared by the tests
    below."""
    config, params = small()
    out = N.keyed_nemesis(config, params, 2**31 + 5)
    history = History([Op(process=r.process, type=r.type, f=r.f,
                          value=r.value, time=r.time, error=r.error)
                       for r in out["records"]], reindex=True)
    was = RECORDER.enabled
    RECORDER.enable()
    RECORDER.clear()
    before = batch_stats()
    try:
        res = core.analyze({"checker": independent.checker(
            linearizable(get_model("cas-register")))}, history)
        events = RECORDER.snapshot()
    finally:
        RECORDER.enabled = was
        RECORDER.clear()
    return {"records": out["records"], "history": history, "result": res,
            "events": events,
            "stats": {k: v - before[k] for k, v in batch_stats().items()}}


def test_checker_agrees_with_the_reference_and_the_host_oracle(analyzed):
    res = analyzed["result"]
    want = {k: wgl_register.check(v)
            for k, v in H.split_keys(analyzed["records"]).items()}
    subs = independent.subhistories(analyzed["history"])
    cpu = get_model("cas-register").cpu_model()
    assert res["key-count"] == len(want) == 24
    assert sum(not w["valid"] for w in want.values()) == 3
    assert res["valid"] is False
    assert sorted(res["failures"]) == sorted(k for k, w in want.items()
                                             if not w["valid"])
    for k, w in want.items():
        got = res["results"][k]
        host = wgl_cpu.check(cpu, subs[k])
        assert got["valid"] is w["valid"] is host["valid"], k
        assert "fallback-chain" not in got and "fallback" not in got
        assert got["analyzer"] == "wgl-tpu-batch"
        if not w["valid"]:
            assert got["op"]["index"] == w["op_index"] \
                == host["op"]["index"], k
            assert got["witness"]["valid"] is False


def test_batch_stats_and_spans_count_the_passes(analyzed):
    names = {}
    for e in analyzed["events"]:
        names.setdefault(e["name"], []).append(e)
    stats = analyzed["stats"]
    passes = names["drivers.run_lanes"]
    retries = names.get("drivers.lane_retry", [])
    assert len(passes) >= 2, "no lane overflowed 256"
    assert len(retries) > 0
    assert set(stats) == {"events_useful", "events_dispatched"}
    for key in stats:
        assert stats[key] == sum(p["args"][key] for p in passes) > 0
    for p in passes:
        assert p["args"]["dispatches"] == sum(
            e["name"] == "drivers.dispatch"
            and e.get("parent-span-id") == p["span-id"]
            for e in analyzed["events"])
    # a pass spans its padded lanes to the furthest cursor; useful are the
    # events of the lanes it answered: a lane sent up a rung counts for
    # nothing until the pass that answers it, and every valid lane is
    # answered once, so the call's useful events are the lanes' own
    model = get_model("cas-register")
    own = {k: len(prepare(h, model)) for k, h in
           independent.subhistories(analyzed["history"]).items()}
    refuted = set(analyzed["result"]["failures"])
    assert passes[0]["args"]["lanes"] == 24
    sent_up = {e["args"]["lane"] for e in retries
               if e["args"]["cap_from"] == 256}
    stayed = [n for lane, (k, n) in enumerate(own.items())
              if lane not in sent_up and k not in refuted]
    assert 24 * max(stayed) <= passes[0]["args"]["events_dispatched"] \
        <= 24 * max(own.values())
    for p in passes:
        assert p["args"]["events_dispatched"] % p["args"]["lanes"] == 0
    valid_own = sum(n for k, n in own.items() if k not in refuted)
    assert valid_own < stats["events_useful"] <= sum(own.values())
    assert stats["events_useful"] < stats["events_dispatched"]
    # a retry goes one rung up, from the capacity its pass had
    caps = [p["args"]["cap"] for p in passes]
    assert caps == sorted(caps) and caps[0] == 256
    assert Counter(e["args"]["cap_from"] for e in retries) == Counter(
        {c: passes[i + 1]["args"]["lanes"] for i, c in enumerate(caps[:-1])})
    assert all(e["args"]["cap_to"] == 8 * e["args"]["cap_from"]
               for e in retries)
    for e in retries:
        batch_span = next(x for x in analyzed["events"]
                          if x.get("span-id") == e["parent-span-id"])
        assert batch_span["name"] == "drivers.check_batch"


def test_batch_stats_is_a_copy():
    before = batch_stats()
    before["events_useful"] += 1000
    assert batch_stats()["events_useful"] == before["events_useful"] - 1000
    assert set(before) == {"events_useful", "events_dispatched"}


def lane_fill(lanes):
    model = get_model("cas-register")
    before = batch_stats()
    res = check_batch(model, lanes)
    assert all(r["valid"] is True for r in res)
    gained = {k: v - before[k] for k, v in batch_stats().items()}
    return gained, [len(prepare(h, model)) for h in lanes]


def test_lane_fill_is_whole_on_even_lanes():
    """Lanes of one length that all fit the first rung: every slot the pass
    spans holds a lane's own event, closure rounds or not."""
    lane = cas_register_history(60, concurrency=4, crash_p=0.0, seed=7)
    gained, own = lane_fill([lane] * 6)
    assert gained["events_useful"] == gained["events_dispatched"] \
        == 6 * own[0] > 0


def test_lane_fill_falls_by_the_padding_of_uneven_lanes():
    lanes = [cas_register_history(n, concurrency=4, crash_p=0.0, seed=7 + n)
             for n in (20, 40, 80)]
    gained, own = lane_fill(lanes)
    assert gained == {"events_useful": sum(own),
                      "events_dispatched": 3 * max(own)}
    assert gained["events_useful"] < 0.7 * gained["events_dispatched"]


# -- the benchmark's plumbing ---------------------------------------------------

def test_offline_plug_finds_the_generator_and_overwrites_nothing(monkeypatch):
    from harness.loops import offline, offline_plug
    old = dict(H.GENERATORS)
    monkeypatch.setattr(H, "GENERATORS", dict(
        (k, v) for k, v in old.items() if k != "keyed_nemesis"))
    monkeypatch.setattr(offline_plug, "GENERATORS", H.GENERATORS)
    traffic = load("traffic", "offline-keyed-nemesis")
    assert traffic["loop"] == "offline_plug"
    offline_plug.register(traffic)
    assert H.GENERATORS["keyed_nemesis"] is N.keyed_nemesis
    for name in ("single_register", "keyed_registers"):
        assert H.GENERATORS[name] is old[name]
    # a module that offers a name already taken does not get it
    fake = types.ModuleType("gen.fake_generators")
    fake.GENERATORS = {"keyed_registers": lambda *a: None,
                       "something_new": lambda *a: None}
    monkeypatch.setitem(sys.modules, "gen.fake_generators", fake)
    offline_plug.register({"generator_module": "fake_generators"})
    assert H.GENERATORS["keyed_registers"] is old["keyed_registers"]
    assert "something_new" in H.GENERATORS
    # and the loop is the offline loop's, with the cell's generator in reach
    seen = {}
    monkeypatch.setattr(offline, "run", lambda cell, *a, **kw: seen.update(
        cell=cell, args=a, kw=kw) or 0)
    cell = types.SimpleNamespace(traffic=traffic)
    assert offline_plug.run(cell, 1, 2.0, False, 0.0, None, x=1) == 0
    assert seen == {"cell": cell, "args": (1, 2.0, False, 0.0, None),
                    "kw": {"x": 1}}


def test_the_refuted_traffic_is_the_keyed_traffic_with_more_refuted():
    old, new = load("traffic", "offline-keyed"), \
        load("traffic", "offline-keyed-refuted")
    assert new["params"].pop("refute_every") == 4
    assert old["params"].pop("refute_every") == 64
    for key in ("name", "why"):
        assert old.pop(key) != new.pop(key)
    assert old == new
    config = dict(load("configs", "keyed-register-200"), keys=16)
    out = H.GENERATORS["keyed_registers"](
        config, dict(new["params"], refute_every=4), 5)
    lanes = H.split_keys(out["records"])
    assert sum(not wgl_register.check(v)["valid"]
               for v in lanes.values()) == 4


def test_program_stats_reader_reads_the_ratio_and_nothing_from_a_parent(
        monkeypatch):
    from readers import program_stats
    args = load("layers", "drivers.lane_fill.keyed")["args"]
    monkeypatch.setattr(batch, "_STATS", {"events_useful": 0,
                                          "events_dispatched": 0})
    assert program_stats.read({}, **args) is None       # nothing ran yet
    monkeypatch.setattr(batch, "_STATS", {"events_useful": 30,
                                          "events_dispatched": 120})
    assert program_stats.read({}, **args) == 25.0
    # a program from before the counter: no function, no key
    assert program_stats.read({}, **dict(
        args, stats="jepsen_tpu.parallel:no_such_stats")) is None
    assert program_stats.read({}, **dict(
        args, stats="jepsen_tpu.no_such_module:batch_stats")) is None
    assert program_stats.read({}, **dict(args, numerator="nope")) is None


# -- the register workload stays what the suites run (PARITY.md) ---------------

def key_histories(wl, concurrency, complete_fn=testkit.perfect_latency):
    h = testkit.simulate({"concurrency": concurrency},
                         gen.clients(wl["generator"]),
                         complete_fn=complete_fn)
    out = {}
    for o in h:
        if o.type == "invoke":
            out.setdefault(o.value[0], []).append(o)
    return out


def test_workload_defaults_are_todays_generator():
    keys = key_histories(linearizable_register.workload(
        keys=[0, 1, 2], ops_per_key=40, threads_per_key=4), 8)
    assert {k: len(v) for k, v in keys.items()} == {0: 40, 1: 40, 2: 40}
    for ops in keys.values():
        by_thread = {}
        for o in ops:
            by_thread.setdefault(o.process % 8, set()).add(o.f)
        assert any(len(fs) > 1 and "read" in fs for fs in by_thread.values())
