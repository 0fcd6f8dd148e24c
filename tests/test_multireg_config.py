"""The ``multireg-10k`` deployment at a small size on the CPU: the
benchmark's generator (``benchmark/gen/multi_register.py``) is the
program's ``synth.multi_register_history`` draw for draw and keeps the
source's shapes under every relabeling; ``linearizable(multi-register)``
through ``core.analyze`` agrees with the benchmark's plain reference
(``benchmark/reference/wgl_multi_register.py``) and with the host oracle,
verdict for verdict and refuting op for refuting op, on the device path;
``wgl_tpu.check_stats()`` and the ``drivers.check`` span sum what the
polls read; and the ``offline_plug`` loop finds the generator.
"""

import json
import os
import random
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from gen import histories as H  # noqa: E402
from gen import multi_register as M  # noqa: E402
from reference import wgl_multi_register  # noqa: E402

from jepsen_tpu import core, synth  # noqa: E402
from jepsen_tpu.checker import wgl_cpu, wgl_tpu  # noqa: E402
from jepsen_tpu.checker.linearizable import linearizable  # noqa: E402
from jepsen_tpu.checker.prep import prepare  # noqa: E402
from jepsen_tpu.history import History, Op  # noqa: E402
from jepsen_tpu.models import MultiRegister, get_model  # noqa: E402
from jepsen_tpu.obs.recorder import RECORDER  # noqa: E402

KEYS, VALUES = 3, 5
SEEDS = [0, 1, 2, 3, 5, 7, 11, 77, 100, 2026, 2**31 + 1, 3_000_000_001]
STAT_KEYS = {"events_consumed", "events_consumed_16k", "cap_events",
             "peak_events"}


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def small(ops=120, concurrency=5):
    """The cell's own files, cut to a history a CPU checks in a second."""
    config = load("configs", "multireg-10k")
    params = load("traffic", "offline-multireg")["params"]
    config.update(ops=ops, concurrency=concurrency)
    params.update(crash_p=0.02)
    return config, params


def as_history(records):
    """The program's input, as ``benchmark/harness/loops/offline.py`` makes
    it from the benchmark's records."""
    return History([Op(process=r.process, type=r.type, f=r.f, value=r.value,
                       time=r.time, error=r.error) for r in records],
                   reindex=True)


def as_records(history):
    return [H.Rec(o.process, o.type, o.f, o.value, o.time, o.error)
            for o in history]


def analyze(history):
    return core.analyze(
        {"checker": linearizable(get_model("multi-register"))}, history)


# -- the generator ------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_generator_is_synths_draw_for_draw(seed):
    kw = dict(keys=KEYS, concurrency=4 + seed % 3, values=VALUES,
              crash_p=0.05, seed=seed, read_p=0.5)
    ours = M.multi_register_history(60 + 20 * (seed % 13), **kw)
    theirs = synth.multi_register_history(60 + 20 * (seed % 13), **kw)
    assert ours == as_records(theirs)


@pytest.mark.parametrize("seed", SEEDS)
def test_every_seed_keeps_the_sources_shapes(seed):
    config, params = small(ops=300, concurrency=6)
    out = M.multi_register(config, params, seed)
    assert out == M.multi_register(config, params, seed)
    assert out["keyed"] is False
    base = M.multi_register(config, params, 4)["records"]
    recs = out["records"]
    # the same structure on every seed: who does what when, on how many keys
    assert [(o.type, o.f, o.time, o.value and len(o.value)) for o in recs] \
        == [(o.type, o.f, o.time, o.value and len(o.value)) for o in base]
    assert sorted({o.process for o in recs}) == list(range(6))
    open_at = {}
    for o in recs:
        if o.type == H.INVOKE:
            ks = [k for k, _ in o.value]
            assert ks == sorted(set(ks)) and 1 <= len(ks) <= KEYS
            assert set(ks) <= set(range(KEYS))
            for _, v in o.value:
                assert (v is None) if o.f == "read" else (0 <= v < VALUES)
            open_at[o.process] = o
            continue
        inv = open_at.pop(o.process)
        assert o.f == inv.f
        if o.type == H.INFO and o.f == "read":
            assert o.value is None          # a crashed read carries no value
        else:
            assert [k for k, _ in o.value] == [k for k, _ in inv.value]
            assert all(v is None or 0 <= v < VALUES for _, v in o.value)
    assert not open_at
    assert sum(o.type == H.INFO for o in recs) >= 2
    assert wgl_multi_register.check(recs) == {"valid": True}


def test_relabeling_permutes_keys_values_and_processes():
    config, params = small(ops=300, concurrency=6)
    seen = {tuple((o.process, repr(o.value)) for o in
                  M.multi_register(config, params, s)["records"])
            for s in SEEDS}
    assert len(seen) == len(SEEDS)
    # a value outside the alphabet stays what it is, None too; the keys
    # stay in [0, 3) and sorted
    odd = H.Rec(0, H.OK, "read", [[0, None], [2, 1007]])
    for s in SEEDS:
        o, = M.relabel([odd], random.Random(s), KEYS, VALUES)
        ks = [k for k, _ in o.value]
        assert ks == sorted(ks) and set(ks) < set(range(KEYS))
        assert sorted(map(repr, (v for _, v in o.value))) == ["1007", "None"]


# -- the system against the plain reference and the host oracle ------------

@pytest.mark.parametrize("seed", SEEDS)
def test_valid_history_on_the_device_path(seed):
    config, params = small()
    recs = M.multi_register(config, params, seed)["records"]
    history = as_history(recs)
    got = analyze(history)
    assert wgl_multi_register.check(recs) == {"valid": True}
    assert wgl_cpu.check(MultiRegister(), history)["valid"] is True
    assert got["valid"] is True
    assert got["analyzer"] == "wgl-tpu"
    assert "fallback-chain" not in got and "fallback" not in got
    # the relabeling does not change the search
    assert got["configs-explored"] == analyze(as_history(
        M.multi_register(config, params, 4)["records"]))["configs-explored"]


@pytest.mark.parametrize("seed", SEEDS)
def test_refuted_history_op_for_op_with_a_witness(seed):
    """One observed key of one ok read flipped to a value outside the
    alphabet (``synth.corrupt_multi_reads``): the three agree on the
    refuting op, and the device's refutation carries the host's witness."""
    config, params = small()
    params.update(history_seed=seed % 1000)
    clean = as_history(M.multi_register(config, params, seed)["records"])
    history = synth.corrupt_multi_reads(clean, n=1, seed=seed, values=VALUES)
    want = wgl_multi_register.check(as_records(history))
    host = wgl_cpu.check(MultiRegister(), history)
    got = analyze(history)
    assert want["valid"] is host["valid"] is got["valid"] is False
    assert got["op"]["index"] == want["op_index"] == host["op"]["index"]
    assert history[want["op_index"]].type == H.INVOKE
    assert got["analyzer"] == "wgl-tpu" and "fallback-chain" not in got
    assert got["witness"]["valid"] is False


def test_a_crashed_write_that_took_effect():
    """The read can only be explained by the crashed write: valid, and the
    control that reads ``info`` as ``fail`` must call it refuted at the
    read.  A crashed read constrains nothing; a nil read is always legal;
    and half a write is no write."""
    recs = [H.Rec(0, H.INVOKE, "write", [[0, 0], [2, 0]]),
            H.Rec(0, H.OK, "write", [[0, 0], [2, 0]]),
            H.Rec(3, H.INVOKE, "write", [[0, 1], [2, 3]]),
            H.Rec(3, H.INFO, "write", [[0, 1], [2, 3]], error="crashed"),
            H.Rec(1, H.INVOKE, "read", [[0, None], [1, None]]),
            H.Rec(1, H.INFO, "read", None, error="crashed"),
            H.Rec(2, H.INVOKE, "read", [[0, None], [1, None], [2, None]]),
            H.Rec(2, H.OK, "read", [[0, 1], [1, None], [2, 3]])]
    assert wgl_multi_register.check(recs) == {"valid": True}
    assert wgl_multi_register.check(recs, info_as_fail=True) == {
        "valid": False, "op_index": 6}
    history = as_history(recs)
    assert wgl_cpu.check(MultiRegister(), history)["valid"] is True
    got = analyze(history)
    assert got["valid"] is True and got["analyzer"] == "wgl-tpu"
    # keys 0 and 2 were set together: 1 beside the older 0 was never there
    torn = recs[:7] + [H.Rec(2, H.OK, "read", [[0, 1], [1, None], [2, 0]])]
    assert wgl_multi_register.check(torn) == {"valid": False, "op_index": 6}
    assert wgl_cpu.check(MultiRegister(), as_history(torn))["valid"] is False
    got = analyze(as_history(torn))
    assert got["valid"] is False and got["op"]["index"] == 6
    assert got["analyzer"] == "wgl-tpu"
    assert got["witness"]["valid"] is False


def test_beam_of_one_answers_false_where_the_search_is_valid():
    config, params = small(ops=300, concurrency=6)
    recs = M.multi_register(config, params, 2**31 + 9)["records"]
    assert wgl_multi_register.check(recs)["valid"] is True
    assert wgl_multi_register.check(recs, beam=1)["valid"] is False


# -- the counters -------------------------------------------------------------

def test_check_stats_sums_what_the_polls_read():
    """A ladder forced to grow from capacity 4: ``check_stats()`` grows by
    the sums over the polls the driver went on from (a chunk re-run at a
    larger capacity is not one), and ``drivers.check`` closes with the
    same four numbers."""
    model = get_model("multi-register")
    h = synth.multi_register_history(120, keys=KEYS, concurrency=5,
                                     crash_p=0.02, seed=3)
    was = RECORDER.enabled
    RECORDER.enable()
    RECORDER.clear()
    before = wgl_tpu.check_stats()
    try:
        res = wgl_tpu.check(model, h, capacity=4, chunk=32)
        events = RECORDER.snapshot()
    finally:
        RECORDER.enabled = was
        RECORDER.clear()
    assert res["valid"] is True and res["max-capacity-reached"] > 4
    gained = {k: v - before[k] for k, v in wgl_tpu.check_stats().items()}
    assert set(gained) == STAT_KEYS
    check, = [e for e in events if e["name"] == "drivers.check"]
    assert check["args"]["grows"] >= 1
    assert {k: check["args"][k] for k in STAT_KEYS} == gained
    polls = [e["args"] for e in events if e["name"] == "drivers.poll"]
    kept = [p for p in polls if not p["overflow"]]
    assert len(kept) < len(polls)
    assert gained["events_consumed"] == sum(p["consumed"] for p in kept) \
        >= len(prepare(h, model))
    assert gained["cap_events"] == sum(p["cap"] * p["consumed"]
                                       for p in kept)
    assert gained["peak_events"] == sum(p["peak"] * p["consumed"]
                                        for p in kept)
    assert 0 < gained["peak_events"] <= gained["cap_events"]
    assert len({p["cap"] for p in kept}) >= 2
    assert gained["events_consumed_16k"] == 0     # far below that rung


def test_check_stats_is_a_copy_and_resets():
    stats = wgl_tpu.check_stats()
    stats["cap_events"] += 1000
    assert wgl_tpu.check_stats()["cap_events"] == stats["cap_events"] - 1000
    wgl_tpu.check(get_model("multi-register"),
                  synth.multi_register_history(40, concurrency=3, seed=1),
                  capacity=64, chunk=64)
    assert wgl_tpu.check_stats()["events_consumed"] > 0
    wgl_tpu.reset_check_stats()
    assert wgl_tpu.check_stats() == dict.fromkeys(STAT_KEYS, 0)


def test_events_at_the_top_rung_are_counted_apart(monkeypatch):
    """``events_consumed_16k`` counts a chunk's events once the capacity
    has reached ``TOP_RUNG``: the rung lowered, so that a CPU test gets
    there."""
    monkeypatch.setattr(wgl_tpu, "TOP_RUNG", 64)
    model = get_model("multi-register")
    h = synth.multi_register_history(120, keys=KEYS, concurrency=5,
                                     crash_p=0.02, seed=3)
    before = wgl_tpu.check_stats()
    res = wgl_tpu.check(model, h, capacity=4, chunk=32)
    gained = {k: v - before[k] for k, v in wgl_tpu.check_stats().items()}
    assert res["max-capacity-reached"] >= 64
    assert 0 < gained["events_consumed_16k"] <= gained["events_consumed"]


# -- the loop finds the generator ---------------------------------------------

def test_offline_plug_finds_the_generator_and_keeps_the_old_ones():
    from harness.loops import offline_plug
    before = {k: v for k, v in H.GENERATORS.items() if k != "multi_register"}
    traffic = load("traffic", "offline-multireg")
    assert (traffic["loop"], traffic["generator_module"],
            traffic["generator"]) == ("offline_plug", "multi_register",
                                      "multi_register")
    offline_plug.register(traffic)
    assert H.GENERATORS["multi_register"] is M.multi_register
    assert {k: v for k, v in H.GENERATORS.items()
            if k != "multi_register"} == before
    assert {"single_register", "keyed_registers"} <= set(before)
