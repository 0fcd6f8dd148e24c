"""The append workload on its normal path and the cell that measures it
(``elle-append10k.offline``, PR 37), at small sizes on the CPU: the system
against the benchmark's plain reference on seeded histories, clean and
corrupted, with the realtime order and without; the reference's O(n)
realtime encoding against the edge-per-pair one; ``append_workload``'s
checker; the generators' key rotation and determinism; ``elle_stats()``;
the benchmark's operation count; the cell's files.  The span names and the
kernel's scopes are ``tests/test_spans.py``'s.
"""

import json
import os
import random
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from gen import list_append as la  # noqa: E402
from harness.loops import offline_elle  # noqa: E402
from harness.loops.offline import program_history  # noqa: E402
from harness.manifest import Cell, plugin  # noqa: E402
from readers import closure_roofline  # noqa: E402
from reference import elle_list_append as ref  # noqa: E402

from jepsen_tpu import core, elle_tpu  # noqa: E402
from jepsen_tpu.checker.elle import ElleListAppend  # noqa: E402
from jepsen_tpu.elle import list_append  # noqa: E402
from jepsen_tpu.elle_tpu import closure, engine  # noqa: E402
from jepsen_tpu.elle_tpu.graphs import pack_group  # noqa: E402
from jepsen_tpu.history import INVOKE, OK, History, Op  # noqa: E402
from jepsen_tpu.workloads import cycle  # noqa: E402

CELL = "elle-append10k.offline"
STRICT = ("strict-serializable",)

#: (transactions, history seed): the benchmark's generator at the cell's
#: shapes of traffic, small
HISTORIES = [(50, 0), (50, 7), (120, 1), (120, 4), (250, 3)]
VARIANTS = [None, "stale_read", "swapped_read", "aborted_read",
            "late_reader"]


def records(n, seed, variant=None):
    recs = la.list_append_history(
        n, concurrency=5, key_count=3, max_writes_per_key=16, seed=seed,
        fail_p=0.1, info_p=0.02)
    if variant is not None:
        recs = la.CORRUPTORS[variant](recs, random.Random(seed))
    return recs


# -- the system against the plain reference ----------------------------------

@pytest.mark.parametrize("realtime", [True, False],
                         ids=["realtime", "no-realtime"])
@pytest.mark.parametrize("variant", VARIANTS, ids=lambda v: v or "clean")
@pytest.mark.parametrize("n,seed", HISTORIES)
def test_system_agrees_with_the_reference(n, seed, variant, realtime):
    recs = records(n, seed, variant)
    want = ref.check(recs, realtime=realtime)
    got = elle_tpu.check(program_history(recs), realtime=realtime)
    verdict = offline_elle.compare([got], want, got["count"], ["elle-tpu"],
                                   ref.decided)
    assert verdict["correct"], (verdict["compared"], got["anomaly-types"],
                                want)
    assert got["valid"] is want["valid"]
    assert got["device-flags"] == want["flags"]
    assert ref.decided(got["anomaly-types"]) == set(want["anomaly_types"])
    # what each corruption is meant to break
    if variant is None:
        assert want["valid"] and not want["anomaly_types"]
    elif variant == "late_reader":       # serializable, not strictly so
        assert want["valid"] is not realtime
        assert want["anomaly_types"] == (["G-single-realtime"]
                                         if realtime else [])
    else:
        assert not want["valid"] or (variant == "stale_read"
                                     and not realtime)
    if variant == "aborted_read":
        assert "G1a" in want["anomaly_types"]
    if variant == "stale_read" and realtime:
        assert want["flags"]["g-single"] and want["flags"]["cyclic"]


@pytest.mark.parametrize("variant", VARIANTS, ids=lambda v: v or "clean")
@pytest.mark.parametrize("n,seed", HISTORIES)
def test_realtime_chain_is_the_edge_per_pair_order(n, seed, variant):
    recs = records(n, seed, variant)
    assert ref.check(recs, realtime=True) == \
        ref.check(recs, realtime=True, rt_edges=ref.realtime_pairs)


def test_realtime_chain_reaches_exactly_the_pairs():
    """Random intervals, some with no known invocation: transaction a
    reaches b through the time nodes iff a completed before b was invoked."""
    rng = random.Random(5)
    points = rng.sample(range(400), 120)
    invoke, complete = [], []
    for a, b in zip(points[::2], points[1::2]):
        invoke.append(min(a, b) if rng.random() > 0.1 else -1)
        complete.append(max(a, b))
    n = len(invoke)
    extra, edges = ref.realtime_chain(invoke, complete)
    assert extra == n and len(edges) <= 3 * n
    succ = ref.adjacency(n + extra, edges)
    _, pairs = ref.realtime_pairs(invoke, complete)
    for a in range(n):
        seen, stack = set(), [a]
        while stack:
            for w in succ[stack.pop()]:
                if w not in seen:
                    seen.add(w)
                    stack.append(w)
        assert {b for b in seen if b < n} == \
            {b for x, b in pairs if x == a}, a


def test_reference_names_only_what_it_decides():
    assert ref.decided(["G-single", "G-single-realtime", "G2-item",
                        "G-nonadjacent-realtime", "G1a"]) == \
        {"G-single", "G1a"}
    assert ref.decided(["G0-realtime", "G1c"]) == {"G0-realtime", "G1c"}
    # two blind appends after two empty reads: write skew, every cycle
    # has two anti-dependencies, so the cycle stays unnamed
    skew = [la.Rec(0, "invoke", "txn", [["r", 1, None], ["append", 0, 1]]),
            la.Rec(1, "invoke", "txn", [["r", 0, None], ["append", 1, 1]]),
            la.Rec(0, "ok", "txn", [["r", 1, []], ["append", 0, 1]]),
            la.Rec(1, "ok", "txn", [["r", 0, []], ["append", 1, 1]])]
    want = ref.check(skew, realtime=False)
    assert want["unnamed_cycle"] and not want["valid"]
    assert want["anomaly_types"] == []
    assert want["flags"] == {"cyclic": True, "g0": False, "g1c": False,
                             "g-single": False}
    got = elle_tpu.check(program_history(skew))
    assert got["anomaly-types"] == ["G2-item"]
    ok = offline_elle.compare([got], want, 2, ["elle-tpu"], ref.decided)
    assert ok["correct"]
    blind = dict(got, **{"anomaly-types": []})
    assert offline_elle.compare([blind], want, 2, ["elle-tpu"], ref.decided)[
        "compared"]["anomaly_mismatches"]["value"] == 1


def test_reference_imports_nothing_of_the_program():
    with open(ref.__file__, encoding="utf-8") as f:
        source = f.read()
    assert "jepsen_tpu" not in source and "import jax" not in source
    with open(la.__file__, encoding="utf-8") as f:
        assert "jepsen_tpu" not in f.read()


# -- the comparison that decides ``correct`` ----------------------------------

def sound(want):
    return {"valid": want["valid"], "anomaly-types": want["anomaly_types"],
            "device-flags": dict(want["flags"]), "count": want["count"],
            "analyzer": "elle-tpu"}


@pytest.mark.parametrize("fault,count", [
    ({"valid": False}, "verdict_mismatches"),
    ({"valid": "unknown"}, "unknown_verdicts"),
    ({"anomaly-types": ["G1a"]}, "anomaly_mismatches"),
    ({"device-flags": {"cyclic": True, "g0": False, "g1c": False,
                       "g-single": False}}, "flag_mismatches"),
    ({"device-flags": None}, "flag_mismatches"),
    ({"analyzer": "elle-cpu"}, "host_answers"),
    ({"fallback-chain": [{"analyzer": "elle-tpu"}]}, "host_answers"),
    ({"cycle-search-truncated": True}, "host_answers"),
    ({"count": 1}, "txn_count_drift"),
])
def test_compare_counts_each_fault_under_its_own_name(fault, count):
    want = ref.check(records(50, 0), realtime=True)
    fine = offline_elle.compare([sound(want)] * 2, want, want["count"],
                                ["elle-tpu"], ref.decided)
    assert fine["correct"] and fine["attempted"] == 2
    assert set(fine["compared"]) == {
        "verdict_mismatches", "anomaly_mismatches", "flag_mismatches",
        "unknown_verdicts", "host_answers", "txn_count_drift"}
    got = offline_elle.compare([sound(want), dict(sound(want), **fault)],
                               want, want["count"], ["elle-tpu"],
                               ref.decided)
    assert not got["correct"] and got["failed"] == 1
    assert {k for k, c in got["compared"].items() if not c["ok"]} == {count}
    assert all(c["limit"] == 0 for c in got["compared"].values())


def test_compare_warm_up_count_and_no_results():
    want = ref.check(records(50, 0), realtime=True)
    drift = offline_elle.compare([sound(want)], want, want["count"] + 1,
                                 ["elle-tpu"], ref.decided)
    assert drift["compared"]["txn_count_drift"]["value"] == 1
    assert not offline_elle.compare([], want, None, ["elle-tpu"],
                                    ref.decided)["correct"]


# -- the workload's normal path ------------------------------------------------

def strip(res):
    return {k: v for k, v in res.items()
            if k not in ("analyzer", "device-flags", "duration-s")}


def test_append_workload_checks_on_the_device_tier():
    h = program_history(records(50, 7, "stale_read"))
    checker = cycle.append_workload()["checker"]
    assert isinstance(checker, ElleListAppend) and checker.engine == "auto"
    assert cycle.AppendChecker is ElleListAppend
    got = core.analyze({"checker": checker}, h)
    assert got["analyzer"] == "elle-tpu" and got["valid"] is False
    assert set(got["device-flags"]) == set(closure.FLAG_NAMES)
    host = list_append.check(h)
    host.pop("anomalies-full"), host.pop("edges-full")
    assert strip(got) == host and "edges-full" not in got


@pytest.mark.parametrize("models,realtime", [
    (None, False), (("serializable",), False),
    (("snapshot-isolation",), False), (STRICT, True),
    (("strict-1sr",), True), (("PL-SS", "serializable"), True),
    (("linearizable",), True)])
def test_realtime_follows_the_models_asked_for(models, realtime):
    checker = cycle.append_workload(consistency_models=models)["checker"]
    assert checker.realtime is realtime
    assert cycle.wr_workload(
        consistency_models=models)["checker"].realtime is realtime
    # an explicit choice still stands
    assert ElleListAppend(consistency_models=models,
                          realtime=not realtime).realtime is not realtime
    h = program_history(records(50, 7, "late_reader"))
    got = core.analyze({"checker": checker}, h)
    # serializable and not strictly so; an anomaly that needs the
    # realtime order refutes only models that speak of it
    assert got["valid"] is not realtime
    assert ref.decided(got["anomaly-types"]) == (
        {"G-single-realtime"} if realtime else set())
    assert got["device-flags"]["cyclic"] is realtime


def test_wr_workload_hands_its_key_orders_to_the_checker():
    wl = cycle.wr_workload(linearizable_keys=True)
    assert wl["checker"].workload == "rw-register"
    assert wl["checker"].workload_kw == {"sequential_keys": False,
                                         "linearizable_keys": True}
    assert cycle.WrChecker is type(wl["checker"])


def test_no_device_gives_the_hosts_result_map(monkeypatch):
    h = program_history(records(50, 7, "stale_read"))
    monkeypatch.setattr(engine, "available", lambda: False)
    got = core.analyze({"checker": cycle.append_workload(
        consistency_models=STRICT)["checker"]}, h)
    assert got["analyzer"] == "elle-cpu" and "device-flags" not in got
    assert "fallback-chain" not in got
    host = list_append.check(h, realtime=True)
    host.pop("anomalies-full"), host.pop("edges-full")
    assert strip(got) == host


def test_a_device_error_degrades_with_its_chain_never_to_a_verdict(
        monkeypatch):
    def broken(n_pad, realtime):
        raise RuntimeError("the chip went away")
    monkeypatch.setattr(engine, "_timed_lane_flags", broken)
    engine.reset_elle_stats()
    recs = records(50, 0)
    got = elle_tpu.check(program_history(recs), realtime=True)
    assert got["analyzer"] == "elle-cpu" and got["valid"] is True
    assert got["fallback-chain"][0]["solver"] == "elle-tpu"
    assert "device-flags" not in got
    stats = engine.elle_stats()
    assert (stats["fallbacks"], stats["recoveries"], stats["groups"]) == \
        (1, 1, 0)
    want = ref.check(recs, realtime=True)
    assert offline_elle.host_answers(got, ["elle-tpu"]) >= 1
    verdict = offline_elle.compare([got], want, None, ["elle-tpu"],
                                   ref.decided)
    assert not verdict["correct"]
    assert not verdict["compared"]["host_answers"]["ok"]


# -- the generators --------------------------------------------------------------

def test_key_pool_rotates_and_bounds_a_keys_writes():
    pool = cycle.KeyPool(3, max_writes_per_key=4, key_dist="exponential",
                         rng=random.Random(1))
    writes = [pool.write() for _ in range(60)]
    per_key = {}
    for k, v in writes:
        per_key.setdefault(k, []).append(v)
    assert all(vs == list(range(1, len(vs) + 1)) and len(vs) <= 4
               for vs in per_key.values())
    assert len(per_key) > 3 and len(pool.active) == 3
    assert all(pool.writes.get(k, 0) < 4 for k in pool.active)
    # today's callers: a fixed key set, no retirement
    fixed = cycle.KeyPool(4, rng=random.Random(2))
    assert {fixed.write()[0] for _ in range(200)} == {0, 1, 2, 3}
    assert fixed.active == [0, 1, 2, 3]
    with pytest.raises(ValueError):
        cycle.KeyPool(3, key_dist="zipf")


def test_exponential_choice_favours_the_last_slots():
    pool = cycle.KeyPool(10, key_dist="exponential", rng=random.Random(3))
    hits = [0] * 10
    for _ in range(20000):
        hits[pool.slot()] += 1
    assert hits[9] > hits[8] > hits[7] > hits[5] > hits[0]
    assert 0.45 < hits[9] / 20000 < 0.55        # weight 2^i: about a half


def test_append_gen_emits_transactions_over_a_rotating_pool():
    rng = random.Random(4)
    g = cycle.append_gen(keys=3, max_writes_per_key=5,
                         key_dist="exponential", rng=rng)
    txns = [g.f()["value"] for _ in range(200)]
    keys = {k for t in txns for _, k, _ in t}
    assert len(keys) > 3
    top = {}
    for t in txns:
        assert 1 <= len(t) <= 4
        for f, k, v in t:
            assert f in ("r", "append")
            if f == "append":
                assert v == top.get(k, 0) + 1 <= 5
                top[k] = v


def test_benchmark_generator_is_the_seeds_and_rotates():
    a = la.list_append_history(300, seed=9, key_count=3,
                               max_writes_per_key=8)
    assert a == la.list_append_history(300, seed=9, key_count=3,
                                       max_writes_per_key=8)
    assert a != la.list_append_history(300, seed=10, key_count=3,
                                       max_writes_per_key=8)
    reads = [len(v) for o in a if o.type == "ok"
             for f, _, v in o.value if f == "r"]
    assert 0 < max(reads) <= 8
    assert len({k for o in a for _, k, _ in o.value}) > 3
    assert {o.type for o in a} >= {"invoke", "ok", "fail"}


def test_the_run_seed_relabels_and_leaves_the_work_alone():
    cell = Cell(CELL)
    cell.config.update(txns=300)
    gen = plugin("gen", cell.traffic["generator_module"], "GENERATORS")[
        cell.traffic["generator"]]
    one = gen(cell.config, cell.traffic["params"], 2**31 + 5)
    same = gen(cell.config, cell.traffic["params"], 2**31 + 5)
    other = gen(cell.config, cell.traffic["params"], 7)
    assert one == same and one["records"] != other["records"]
    assert not one["keyed"]
    e1 = elle_tpu.encode(program_history(one["records"]))
    e2 = elle_tpu.encode(program_history(other["records"]))
    for name in ("src", "dst", "invoke", "complete"):
        assert (getattr(e1, name) == getattr(e2, name)).all(), name
    assert ref.check(one["records"])["valid"]


# -- the host pass in two halves ----------------------------------------------

@pytest.mark.parametrize("variant", VARIANTS, ids=lambda v: v or "clean")
def test_the_first_half_encodes_what_the_whole_analysis_would(variant):
    """``dependencies`` is all the device is handed: its edge list, repeats
    and all, encodes to the arrays the finished analysis' graph gives."""
    from jepsen_tpu.elle_tpu.encode import encode_analysis
    h = program_history(records(120, 4, variant))
    deps = list_append.dependencies(h)
    a = list_append.analysis_of(deps)
    half = encode_analysis(deps, "list-append")
    assert half.analysis is None and half.dependencies is deps
    # the finished analysis' graph, one cell a pair of each kind
    graph = {(x, y, k) for x, ys in a.graph.out.items()
             for y, ks in ys.items() for k in ks}
    assert {(int(x), int(y), list_append.EDGE_KINDS[k]) for k in range(3)
            for x, y in zip(half.src[k], half.dst[k]) if x >= 0} == graph
    assert half.n == a.count
    assert half.complete[:a.count].tolist() == [i for i, _ in a.oks]
    it = iter(deps.edges)
    kinds = {(x, y, list_append.EDGE_KINDS[k]) for x, y, k in zip(it, it, it)}
    assert kinds == graph
    # the second half builds no graph; the analysis does once asked
    assert half.finish_analysis() is half.finish_analysis()
    assert "graph" not in vars(half.analysis)
    assert {(x, y, k) for x, ys in half.analysis.graph.out.items()
            for y, ks in ys.items() for k in ks} == graph


def test_a_repeated_edge_is_one_cell_of_its_layer():
    from jepsen_tpu.elle_tpu.encode import encode_analysis
    deps = list_append.dependencies(program_history(records(50, 0)))
    once = encode_analysis(deps, "list-append")
    deps.edges.extend(list(deps.edges))
    twice = encode_analysis(deps, "list-append")
    assert (once.src == twice.src).all() and (once.dst == twice.dst).all()


def test_the_second_half_runs_with_and_without_a_device(monkeypatch):
    """The analysis is finished once a lane's closures are dispatched, or
    at once where no device takes them: the result is the same map."""
    h = program_history(records(120, 1, "aborted_read"))
    on = elle_tpu.check(h, realtime=True)
    off = elle_tpu.check(h, realtime=True, engine="cpu")
    assert on["analyzer"] == "elle-tpu" and off["analyzer"] == "elle-cpu"
    assert "G1a" in on["anomaly-types"]
    # (an acyclic lane's edge list leaves the realtime layer out)
    drop = ("analyzer", "device-flags", "edges-full")
    assert {k: v for k, v in on.items() if k not in drop} == \
        {k: v for k, v in off.items() if k not in drop}


# -- the counter -------------------------------------------------------------------

def test_elle_stats_count_the_shapes_and_the_rounds():
    engine.reset_elle_stats()
    assert engine.elle_stats() == {
        "calls": 0, "lanes": 0, "groups": 0, "n_pad": 0, "e_pad": 0,
        "closure_rounds": 0, "closure_rounds_cap": 0, "layer_builds": 0,
        "cyclic_lanes": 0, "recoveries": 0, "fallbacks": 0}
    clean = program_history(records(150, 1))
    bad = program_history(records(150, 1, "stale_read"))
    enc = elle_tpu.encode(clean)
    res = elle_tpu.check_batch([clean, bad, clean], realtime=True)
    assert [r["valid"] for r in res] == [True, False, True]
    stats = engine.elle_stats()
    n_pad = 160                               # 135 ok transactions
    assert enc.n <= n_pad < enc.n + 32
    assert stats["n_pad"] == n_pad and stats["e_pad"] % 64 == 0
    assert stats["e_pad"] >= enc.src.shape[1]
    cap = closure.closure_rounds(n_pad)
    assert cap == 8
    # the device ran each closure for its slowest lane, on every lane
    packed = pack_group([enc, elle_tpu.encode(bad), enc], n_pad=n_pad)
    most = np.max([np_lane(packed, i, n_pad, True)[1] for i in range(3)],
                  axis=0)
    assert 3 <= most.min() and most.max() < cap
    assert stats == {
        "calls": 1, "lanes": 3, "groups": 1, "n_pad": n_pad,
        "e_pad": stats["e_pad"], "closure_rounds": 3 * int(most.sum()),
        "closure_rounds_cap": 3 * 3 * cap, "layer_builds": 3 * 3,
        "cyclic_lanes": 1, "recoveries": 1, "fallbacks": 0}
    elle_tpu.check(clean, engine="cpu")
    after = engine.elle_stats()
    assert (after["calls"], after["lanes"], after["groups"],
            after["recoveries"], after["closure_rounds"]) == (
        2, 4, 1, 2, stats["closure_rounds"])


def test_a_group_that_falls_back_adds_no_rounds(monkeypatch):
    engine.reset_elle_stats()

    def unreadable(n_pad, realtime):
        def run(*args):
            class Summary:
                def __array__(self, dtype=None, copy=None):
                    raise RuntimeError("the read-back failed")
            return None, None, Summary()
        return run
    monkeypatch.setattr(engine, "_timed_lane_flags", unreadable)
    got = elle_tpu.check(program_history(records(50, 0)), realtime=True)
    assert got["analyzer"] == "elle-cpu" and "fallback-chain" in got
    stats = engine.elle_stats()
    assert (stats["groups"], stats["fallbacks"], stats["closure_rounds"],
            stats["closure_rounds_cap"]) == (1, 1, 0, 0)


# -- the kernel's stop: each closure squares until it stops changing ---------

def np_closure(adj, cap):
    """``adj`` closed by squaring until a squaring changes nothing (that
    one counted) or ``cap`` have run: ``(closure, squarings)``."""
    r, k = adj.astype(bool), 0
    while k < cap:
        nxt = r | ((r.astype(np.int64) @ r.astype(np.int64)) > 0)
        k += 1
        if (nxt == r).all():
            break
        r = nxt
    return r, k


def np_lane(packed, i, n_pad, realtime):
    """One packed lane's four flags and its closures' squarings
    (``closure.CLOSURE_NAMES`` order), from the arrays alone."""
    layers = []
    for kind in range(3):
        m = np.zeros((n_pad, n_pad), bool)
        for s, d in zip(packed["src"][i, kind], packed["dst"][i, kind]):
            if s >= 0:
                m[s, d] = True
        layers.append(m)
    ww, wr, rw = layers
    inv, comp = packed["invoke"][i], packed["complete"][i]
    rt = ((comp[:, None] < inv[None, :]) & (inv[None, :] >= 0)
          if realtime else np.zeros_like(ww))
    cap = closure.closure_rounds(n_pad)
    cl_g0, k_g0 = np_closure(ww | rt, cap)
    cl_nonrw, k_nonrw = np_closure(ww | wr | rt, cap)
    cl_full, k_full = np_closure(ww | wr | rw | rt, cap)
    flags = [bool(cl_full.diagonal().any()), bool(cl_g0.diagonal().any()),
             bool(cl_nonrw.diagonal().any()), bool((rw & cl_nonrw.T).any())]
    return flags, [k_g0, k_nonrw, k_full]


@pytest.mark.parametrize("realtime", [True, False],
                         ids=["realtime", "no-realtime"])
@pytest.mark.parametrize("variant", [None, "stale_read", "late_reader"],
                         ids=lambda v: v or "clean")
@pytest.mark.parametrize("n,seed", [(120, 1), (250, 3)])
def test_kernel_stops_at_the_fixpoint(n, seed, variant, realtime):
    """Flags and squarings of the kernel against numpy's closure run to
    its fixpoint: the confirming squaring counted, none past the cap."""
    enc = elle_tpu.encode(program_history(records(n, seed, variant)))
    n_pad = 288                    # cap 9, and room for every path here
    packed = pack_group([enc], n_pad=n_pad)
    flags, rounds, summary = closure.lane_flags_fn(n_pad, realtime)(
        *(packed[k] for k in ("src", "dst", "invoke", "complete")))
    want_flags, want_rounds = np_lane(packed, 0, n_pad, realtime)
    assert np.asarray(flags)[0].tolist() == want_flags
    assert np.asarray(rounds)[0].tolist() == want_rounds
    assert np.asarray(summary).tolist() == [sum(want_flags)] + want_rounds
    assert rounds.dtype == summary.dtype == np.int32
    assert all(k < closure.closure_rounds(n_pad) for k in want_rounds)
    if variant is not None and realtime:
        assert want_flags[0] and want_flags[3]      # cyclic, g-single


@pytest.mark.parametrize("n_pad", [32, 64])
def test_a_path_through_every_node_runs_the_cap_and_is_closed(n_pad):
    cap = closure.closure_rounds(n_pad)
    path = np.eye(n_pad, k=1, dtype=np.float32)      # 0 -> 1 -> ... -> n-1
    closed, k = jax.jit(closure.transitive_closure,
                        static_argnums=1)(path, cap)
    assert int(k) == cap
    assert np.array_equal(np.asarray(closed),
                          np.triu(np.ones((n_pad, n_pad), np.float32), 1))
    short, k = jax.jit(closure.transitive_closure,
                       static_argnums=1)(path, cap - 1)
    assert int(k) == cap - 1 and np.asarray(short).sum() < closed.sum()
    # the lane: the path in ww, closed into a ring by one wr edge
    src = np.full((1, 3, 64), -1, np.int32)
    dst = np.full((1, 3, 64), -1, np.int32)
    src[0, 0, :n_pad - 1], dst[0, 0, :n_pad - 1] = \
        np.arange(n_pad - 1), np.arange(1, n_pad)
    src[0, 1, 0], dst[0, 1, 0] = n_pad - 1, 0
    times = np.full((1, n_pad), -1, np.int32)
    flags, rounds, summary = closure.lane_flags_fn(n_pad, False)(
        src, dst, times, times)
    # cyclic and g1c: the ring's n_pad edges; not g0 (no wr there)
    assert np.asarray(flags)[0].tolist() == [True, False, True, False]
    assert np.asarray(rounds)[0].tolist() == [cap] * 3
    assert np.asarray(summary).tolist() == [2, cap, cap, cap]


def append_chain(n):
    """``n`` appends to one key, one after another, then a read of all:
    a ww path of ``n - 1`` edges and a wr edge to the reader."""
    ops = []
    for i in range(1, n + 1):
        ops += [Op(process=i % 5, type=INVOKE, f="txn",
                   value=[["append", "x", i]]),
                Op(process=i % 5, type=OK, f="txn",
                   value=[["append", "x", i]])]
    read = [["r", "x", list(range(1, n + 1))]]
    ops += [Op(process=0, type=INVOKE, f="txn", value=read),
            Op(process=0, type=OK, f="txn", value=read)]
    return History(ops, reindex=True)


def test_a_group_costs_its_slowest_lanes_rounds_on_every_lane():
    """Vmapped, a loop runs until its last lane stops: the short lane's
    own rounds stay its own, the device's work is the long lane's."""
    long, short = append_chain(24), append_chain(2)
    n_pad = 256                                   # cap 8
    encs = [elle_tpu.encode(long), elle_tpu.encode(short)]
    packed = pack_group(encs, n_pad=n_pad)
    _, rounds, summary = closure.lane_flags_fn(n_pad, False)(
        *(packed[k] for k in ("src", "dst", "invoke", "complete")))
    want = [np_lane(packed, i, n_pad, False)[1] for i in range(2)]
    assert want == [[6, 6, 6], [1, 2, 2]]   # paths of 23, 24; 1, 2 edges
    assert np.asarray(rounds).tolist() == want
    assert np.asarray(summary).tolist() == [0, 6, 6, 6]
    engine.reset_elle_stats()
    res = elle_tpu.check_batch([long, short], n_pad_floor=n_pad)
    assert [r["valid"] for r in res] == [True, True]
    stats = engine.elle_stats()
    assert stats["n_pad"] == n_pad
    assert (stats["closure_rounds"], stats["closure_rounds_cap"]) == (
        2 * 18, 2 * 3 * 8)


def test_a_sharded_group_runs_its_slowest_lane_on_every_shard():
    """Over a mesh the lanes are padded to the shards and the loop stops
    when the last lane of any shard does: the padding lanes count too."""
    from jax.sharding import Mesh
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    hs = [append_chain(24), append_chain(2), append_chain(5)]
    engine.reset_elle_stats()
    res = elle_tpu.check_batch(hs, mesh=mesh, n_pad_floor=256)
    assert [r["valid"] for r in res] == [True] * 3
    assert [r["device-flags"]["cyclic"] for r in res] == [False] * 3
    stats = engine.elle_stats()
    assert (stats["closure_rounds"], stats["closure_rounds_cap"]) == (
        4 * 18, 4 * 3 * 8)


def test_the_change_test_sees_one_cell_past_two_to_the_24():
    """One more set cell among 4,097^2 (over 2^24, where float32 steps by
    2) is seen by the kernel's test and by the stream checker's count."""
    from jepsen_tpu.elle_tpu import incremental
    n = 4097
    full = jnp.ones((n, n), jnp.float32)
    less = full.at[n - 1, 0].set(0.0)
    assert n * n > 2 ** 24
    grew = jax.jit(closure.grew)
    assert bool(grew(full, less)) and not bool(grew(full, full))
    assert grew(full[None], less[None]).shape == (1,)
    count = jax.jit(incremental.set_cells)
    assert count(full).dtype == jnp.int32
    assert int(count(full)) - int(count(less)) == 1
    square = jax.eval_shape(incremental._square_fn(32),
                            *[jax.ShapeDtypeStruct((32, 32), jnp.float32)]
                            * 3)
    assert square[3].dtype == jnp.int32


@pytest.mark.parametrize("variant", ["stale_read", "late_reader"])
def test_a_spent_budget_cuts_the_realtime_layer_and_keeps_the_flags(variant):
    """What the cell's probes ask of a refuted 10,000-transaction history:
    the device's flags, and a recovery that stops where its budget does."""
    h = program_history(records(120, 1, variant))
    want = ref.check(records(120, 1, variant), realtime=True)
    got = ElleListAppend(consistency_models=STRICT).check(
        {"name": "t"}, h, {"budget_s": 0.0})
    assert got["valid"] == "unknown" and got["cycle-search-truncated"]
    assert got["analyzer"] == "elle-tpu" and "fallback-chain" not in got
    assert got["device-flags"] == want["flags"] and want["flags"]["cyclic"]
    full = ElleListAppend(consistency_models=STRICT).check({"name": "t"}, h)
    assert full["valid"] is False and full["device-flags"] == want["flags"]


def test_add_realtime_edges_asks_its_budget_once_a_row():
    from jepsen_tpu.elle.graph import SearchBudget
    a = list_append.analyze(program_history(records(50, 0)))
    spent = SearchBudget(deadline_s=-1.0)
    before = sum(len(bs) for bs in a.graph.out.values())
    list_append.add_realtime_edges(a.graph, a.oks, a.pairs, budget=spent)
    assert spent.truncated
    assert sum(len(bs) for bs in a.graph.out.values()) == before
    roomy = SearchBudget(deadline_s=60.0)
    steps = roomy.steps
    list_append.add_realtime_edges(a.graph, a.oks, a.pairs, budget=roomy)
    assert not roomy.truncated and roomy.steps == steps
    assert sum(len(bs) for bs in a.graph.out.values()) > before


def test_gsingle_search_charges_its_budget_once_a_search():
    """A return path is looked for without realtime edges first, then with
    them: two searches where the first finds none, and two charges."""
    from jepsen_tpu.elle.graph import Graph, SearchBudget, gsingle_cycles
    g = Graph()
    g.add_edge(0, 1, "rw")
    g.add_edge(1, 0, "realtime")          # the only way back is realtime
    budget = SearchBudget()
    steps = budget.steps
    assert gsingle_cycles(g, budget=budget) == [[0, 1, 0]]
    assert steps - budget.steps == 2 * len(g)
    g.add_edge(1, 0, "wr")                # a plain way back: one search
    steps = budget.steps
    assert gsingle_cycles(g, budget=budget) == [[0, 1, 0]]
    assert steps - budget.steps == len(g)
    tight = SearchBudget(max_steps=len(g))
    g2 = Graph()
    g2.add_edge(0, 1, "rw")
    g2.add_edge(1, 0, "realtime")
    assert gsingle_cycles(g2, budget=tight) == [] and tight.truncated


def test_a_lane_past_the_cell_budget_goes_alone():
    assert engine.group_cap(64) > 1
    assert engine.group_cap(4096) == 1
    assert engine.group_cap(9504) == 1          # the cell's lane


# -- the benchmark's operation count and files ----------------------------------------

def test_flops_against_a_hand_count():
    # the cell's call: three closures of 14 squarings at 9,504, three
    # one-hot products over 19,136 edge slots
    call = {"n_pad": 9504, "e_pad": 19136, "closure_rounds": 42,
            "layer_builds": 3, "calls": 1}
    by_hand = 42 * 2 * 9504 ** 3 + 3 * 2 * 19136 * 9504 ** 2
    assert closure_roofline.flops(call) == by_hand
    assert 8.2e13 < by_hand < 8.3e13
    assert closure.closure_rounds(9504) == 14

    class Trace:
        busy_s = 2.0
    ctx = {"trace": Trace, "counters": {"calls": 3},
           "device": {"kind": "TPU v5 lite"}}
    engine.reset_elle_stats()
    args = {"stats": "jepsen_tpu.elle_tpu.engine:elle_stats",
            "peak": "bf16_flops_per_s"}
    assert closure_roofline.read(ctx, **args) is None       # no call yet
    with engine._STATS_LOCK:
        engine._STATS.update(call, calls=5, closure_rounds=5 * 42,
                             layer_builds=5 * 3)
    share = closure_roofline.read(ctx, **args)
    assert share == pytest.approx(100 * 3 * by_hand / (2.0 * 197e12))
    engine.reset_elle_stats()
    # a program from before the counter, a cell on no known device
    assert closure_roofline.read(
        ctx, "jepsen_tpu.elle_tpu.engine:no_such_stats",
        "bf16_flops_per_s") is None
    assert closure_roofline.read(dict(ctx, device={}), **args) is None


def test_closure_rounds_share_reads_the_rounds_over_the_cap(monkeypatch):
    """``kernels.closure_rounds_share``: the squarings run over those the
    cap allows, as a share; nothing on a program without the cap's key."""
    spec = next(m for m in Cell(CELL).per_layer()
                if m["name"] == "kernels.closure_rounds_share")
    assert (spec["reader"], spec["unit"], spec["better"]) == (
        "program_stats", "%", "lower")
    read = plugin("readers", spec["reader"], "read")
    engine.reset_elle_stats()
    assert read({}, **spec["args"]) is None                  # no call yet
    with engine._STATS_LOCK:
        engine._STATS.update(calls=2, closure_rounds=2 * 12,
                             closure_rounds_cap=2 * 42)
    assert read({}, **spec["args"]) == pytest.approx(100 * 12 / 42)
    parent = {k: v for k, v in engine.elle_stats().items()
              if k != "closure_rounds_cap"}
    monkeypatch.setattr(engine, "elle_stats", lambda: parent)
    assert read({}, **spec["args"]) is None
    engine.reset_elle_stats()


def test_the_cells_files_load_through_the_manifest():
    cell = Cell(CELL)
    assert cell.chips == 1
    assert (cell.entry["config"], cell.entry["traffic"]) == (
        "elle-append-10k", "offline-elle-append")
    # the host's third of a call moves with the machine's other tenants:
    # the cell reports what the issue names, with the device-bound cells
    assert {m["name"] for m in cell.end_to_end()} == {"verdict_s", "setup_s"}
    assert cell.traffic["verdict_metric"] == "verdict_s"
    mine = {m["name"] for m in cell.per_layer()}
    assert all(m["moves"] in ("verdict_s", "setup_s")
               for m in cell.per_layer())
    assert {"elle.host_pass_s", "elle.readback_wait_share",
            "kernels.closure_mxu_share", "kernels.closure_rounds_share",
            "entry.host_answers",
            "device.idle_share", "device.peak_hbm_bytes",
            "drivers.launches_per_call", "compile.window_compiles",
            "compile.setup_cache_misses", "setup.warmup_excess_s",
            "compile.trace_s", "compile.lower_s", "compile.load_s",
            "compile.eager_s", "setup.warmup_unnamed_s"} == mine
    for m in cell.per_layer():
        assert callable(plugin("readers", m["reader"], "read")), m["name"]
    config, traffic = cell.config, cell.traffic
    entry = next(c for c in cell.manifest["configs"]
                 if c["name"] == "elle-append-10k")
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == config["reduced"] == []
    assert config["architecture"] is None
    assert config["consistency_models"] == ["strict-serializable"]
    assert (config["txns"], config["concurrency"], config["key_count"],
            config["max_writes_per_key"], config["max_txn_length"]) == (
        10000, 10, 10, 256, 4)
    assert traffic["loop"] == "offline_elle"
    assert traffic["requires"] == ["jepsen_tpu.elle_tpu.engine:elle_stats"]
    assert set(traffic["probes"]["corruptors"]) <= set(la.CORRUPTORS)
    assert traffic["probes"]["budget_s"] > 0
    assert callable(plugin("harness.loops", traffic["loop"], "run"))
    checker = offline_elle.program_checker(traffic["entry"],
                                           config["consistency_models"])
    assert isinstance(checker, ElleListAppend) and checker.realtime
    assert callable(plugin("reference", config["reference"], "check"))
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        assert len(f.read()) < 64 * 1024
    json.dumps(config)
