"""The wr (read/write-register) workload on its normal path and the cell that
measures it (``elle-wr10k.offline``), at small sizes on the CPU: the system
against the benchmark's plain reference on seeded histories, clean and
corrupted, with the realtime order and without; the host pass in two halves
against the one-piece pass it replaced; ``linearizable_keys`` following the
models asked for; the generator; the span; the cell's files
and a toy run of its loop.
"""

import bisect
import json
import os
import random
import sys
import time
from collections import defaultdict
from functools import cached_property
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from gen import list_append as la  # noqa: E402
from gen import rw_register as wr  # noqa: E402
from harness import report  # noqa: E402
from harness.loops import offline_elle, offline_requires  # noqa: E402
from harness.loops.offline import program_history  # noqa: E402
from harness.manifest import Cell, plugin  # noqa: E402
from reference import elle_rw_register as ref  # noqa: E402

from jepsen_tpu import core, elle_tpu, synth  # noqa: E402
from jepsen_tpu.checker.core import resolve_checker  # noqa: E402
from jepsen_tpu.checker.elle import ElleRwRegister  # noqa: E402
from jepsen_tpu.elle import rw_register  # noqa: E402
from jepsen_tpu.elle.graph import Graph  # noqa: E402
from jepsen_tpu.elle.list_append import (  # noqa: E402
    Analysis, collect_cycle_anomalies, finish_result)
from jepsen_tpu.history import FAIL, INVOKE, OK, History, Op  # noqa: E402
from jepsen_tpu.txn import READ_FS, WRITE_FS  # noqa: E402
from jepsen_tpu.workloads import cycle  # noqa: E402

CELL = "elle-wr10k.offline"
STRICT = ("strict-serializable",)

#: (transactions, history seed): the benchmark's generator at the cell's
#: shapes of traffic, small
HISTORIES = [(50, 7), (120, 1), (120, 4)]
VARIANTS = [None, "stale_read", "future_read"]
#: one kernel shape for every history here
N_PAD = 320


def records(n, seed, variant=None):
    recs = wr.to_register(la.list_append_history(
        n, concurrency=5, key_count=3, max_writes_per_key=16, seed=seed,
        fail_p=0.1, info_p=0.02))
    if variant is not None:
        recs = wr.CORRUPTORS[variant](recs, random.Random(seed))
    return recs


# -- the system against the plain reference ----------------------------------

@pytest.mark.parametrize("realtime", [True, False],
                         ids=["realtime", "no-realtime"])
@pytest.mark.parametrize("variant", VARIANTS, ids=lambda v: v or "clean")
@pytest.mark.parametrize("n,seed", HISTORIES)
def test_system_agrees_with_the_reference(n, seed, variant, realtime):
    recs = records(n, seed, variant)
    want = ref.check(recs, realtime=realtime)
    got = elle_tpu.check(program_history(recs), workload="rw-register",
                         realtime=realtime, linearizable_keys=realtime,
                         n_pad_floor=N_PAD)
    verdict = offline_elle.compare([got], want, got["count"], ["elle-tpu"],
                                   ref.decided)
    assert verdict["correct"], (verdict["compared"], got["anomaly-types"],
                                want)
    assert got["valid"] is want["valid"]
    assert got["device-flags"] == want["flags"]
    assert ref.decided(got["anomaly-types"]) == set(want["anomaly_types"])
    # what each corruption is meant to break, with the keys' realtime order
    if variant is None or not realtime:
        assert want["valid"] or variant is not None
    elif variant == "stale_read":
        assert want["flags"]["cyclic"] and want["flags"]["g-single"]
    else:
        assert want["flags"]["cyclic"] and want["flags"]["g1c"]


def test_a_stale_read_needs_the_keys_realtime_order():
    """The stale read breaks no order but the keys' realtime one: checked
    as serializable (no linearizable keys) the same history passes."""
    recs = records(120, 1, "stale_read")
    assert not ref.check(recs, realtime=True)["valid"]
    assert ref.check(recs, realtime=False)["valid"]
    h = program_history(recs)
    assert elle_tpu.check(h, workload="rw-register")["valid"] is True
    got = core.analyze({"checker": cycle.wr_workload(
        consistency_models=STRICT)["checker"]}, h)
    assert got["valid"] is False and got["analyzer"] == "elle-tpu"


def test_reference_imports_nothing_of_the_program():
    for mod in (ref, wr):
        with open(mod.__file__, encoding="utf-8") as f:
            source = f.read()
        assert "jepsen_tpu" not in source and "import jax" not in source


@pytest.mark.parametrize("seed", range(4))
def test_the_sparse_order_is_the_covering_pairs_of_every_pair(seed):
    """The linearizable-keys order the program keeps is exactly the
    covering pairs of the all-pairs rule (b after a with no write between
    them), key by key: what the reference keeps, found another way."""
    rng = random.Random(seed)
    key, invoke, complete = [], [], []
    for _ in range(150):
        a, b = sorted(rng.sample(range(2000), 2))
        key.append(rng.randrange(4))
        invoke.append(a)
        complete.append(b)
    arr = [np.array(x, np.int64) for x in (key, invoke, complete)]
    a, b = rw_register._linearizable(*arr, 2001)
    got = set(zip(a.tolist(), b.tolist()))
    n = len(key)
    before = {(x, y) for x in range(n) for y in range(n)
              if key[x] == key[y] and complete[x] < invoke[y]}
    covering = {(x, y) for x, y in before
                if not any((x, z) in before and (z, y) in before
                           for z in range(n))}
    assert got == covering
    assert len(got) < len(before)


# -- the host pass in two halves against the one-piece pass --------------------

def one_piece_analyze(history, sequential_keys=False,
                      linearizable_keys=False):
    """The register pass as one piece, in dicts and sets: the program's
    before it was cut in two halves, kept here as the oracle."""
    history = history.client_ops()
    pairs = history.pair_index()
    oks, failed_writes = [], set()
    for i, op in enumerate(history):
        if not isinstance(op.value, (list, tuple)):
            continue
        if op.type == OK:
            oks.append((i, op))
        elif op.type == FAIL:
            j = pairs[i]
            txn = op.value or (history[j].value if j >= 0 else None)
            for f, k, v in txn or ():
                if f in WRITE_FS:
                    failed_writes.add((k, v))
    anomalies = defaultdict(list)
    writer, txn_of, intermediate = {}, {}, {}
    for tid, (_, op) in enumerate(oks):
        txn_of[tid] = op.value
        last_w = {}
        for f, k, v in op.value:
            if f in WRITE_FS:
                if (k, v) in writer:
                    anomalies["duplicate-writes"].append({"key": k,
                                                          "value": v})
                writer[(k, v)] = tid
                if k in last_w:
                    intermediate[(k, last_w[k])] = tid
                last_w[k] = v
    vg = defaultdict(lambda: defaultdict(set))
    for tid, (_, op) in enumerate(oks):
        reads, last_w = {}, {}
        for f, k, v in op.value:
            if f in READ_FS:
                reads[k] = v
            elif f in WRITE_FS:
                if k in last_w:
                    vg[k][last_w[k]].add(v)
                elif k in reads and reads[k] != v:
                    vg[k][reads[k]].add(v)
                last_w[k] = v
    for (k, v) in writer:
        if v is not None:
            vg[k][None].add(v)
    if sequential_keys or linearizable_keys:
        writes = defaultdict(list)
        for i, op in oks:
            inv = pairs[i] if pairs[i] >= 0 else i
            last = {k: v for f, k, v in op.value if f in WRITE_FS}
            for k, v in last.items():
                writes[k].append((min(i, inv), max(i, inv), op.process, v))
        for k, ws in writes.items():
            if sequential_keys:
                by_proc = defaultdict(list)
                for w in ws:
                    by_proc[w[2]].append(w)
                for plist in by_proc.values():
                    plist.sort(key=lambda w: w[0])
                    for a, b in zip(plist, plist[1:]):
                        if a[3] != b[3]:
                            vg[k][a[3]].add(b[3])
            if linearizable_keys:
                ws_sorted = sorted(ws, key=lambda w: w[0])
                n = len(ws_sorted)
                suf_min = [0] * (n + 1)
                suf_min[n] = float("inf")
                for i in range(n - 1, -1, -1):
                    suf_min[i] = min(ws_sorted[i][1], suf_min[i + 1])
                invokes = [w[0] for w in ws_sorted]
                for a in ws_sorted:
                    j = bisect.bisect_right(invokes, a[1])
                    if j >= n:
                        continue
                    for b in ws_sorted[j:]:
                        if b[0] > suf_min[j]:
                            break
                        if a[3] != b[3]:
                            vg[k][a[3]].add(b[3])
    for k, adj in vg.items():
        cyc = rw_register._version_cycle(adj)
        if cyc:
            anomalies["cyclic-versions"].append({"key": k, "versions": cyc})
    g = Graph()
    for tid in range(len(oks)):
        g.add_node(tid)
    readers = defaultdict(list)
    for tid, (_, op) in enumerate(oks):
        seen_w = set()
        for f, k, v in op.value:
            if f in READ_FS and k not in seen_w:
                readers[(k, v)].append(tid)
                if (k, v) in failed_writes:
                    anomalies["G1a"].append({"key": k, "value": v,
                                             "reader": op.to_dict()})
                iw = intermediate.get((k, v))
                if iw is not None and iw != tid:
                    anomalies["G1b"].append({"key": k, "value": v,
                                             "reader": op.to_dict()})
                w = writer.get((k, v)) if v is not None else None
                if w is not None and w != tid:
                    g.add_edge(w, tid, "wr")
            elif f in WRITE_FS:
                seen_w.add(k)
    for k, adj in vg.items():
        for v, nexts in adj.items():
            w1 = writer.get((k, v))
            for v2 in nexts:
                w2 = writer.get((k, v2))
                if w2 is None:
                    continue
                if w1 is not None and w1 != w2:
                    g.add_edge(w1, w2, "ww")
                for r in readers.get((k, v), ()):
                    if r != w2:
                        g.add_edge(r, w2, "rw")
    return SimpleNamespace(graph=g, txn_of=txn_of, anomalies=anomalies,
                           oks=oks, pairs=pairs, count=len(oks)), vg


def txn(process, value, outcome=OK):
    return [Op(process=process, type=INVOKE, f="txn", value=value),
            Op(process=process, type=outcome, f="txn", value=value)]


def corner_history():
    """Each host anomaly and each version source at least once: an
    intermediate value read (G1b), a failed write read (G1a), a value
    written twice (duplicate-writes), a transaction writing one value twice
    and two that order a key's values both ways (cyclic-versions), a written
    None, a nemesis entry, and a crashed writer."""
    ops = (txn(0, [["w", "x", 1], ["w", "x", 2]])
           + txn(1, [["r", "x", 1], ["w", "y", 1]])
           + txn(2, [["w", "z", 9]], FAIL)
           + txn(3, [["r", "z", 9], ["r", "x", 2]])
           + txn(4, [["w", "x", 2]])
           + txn(0, [["w", "q", 5], ["w", "q", 5]])
           + [Op(process="nemesis", type="info", f="kill", value=["n1"])]
           + txn(1, [["r", "a", 1], ["w", "a", 2]])
           + txn(2, [["r", "a", 2], ["w", "a", 1]])
           + txn(3, [["w", "y", None], ["r", "y", None]])
           + txn(4, [["w", "b", 7]], "info")
           + txn(0, [["r", "b", 7], ["w", "b", 8], ["r", "b", 8]]))
    return History(ops, reindex=True)


def edges_of(g):
    return {(a, b, k) for a, bs in g.out.items() for b, ks in bs.items()
            for k in ks}


def pass_histories():
    return ([("corner", corner_history())]
            + [(f"synth{s}", synth.rw_register_history(
                n_txns=40, keys=3, concurrency=5, seed=s,
                anomaly_p=0.3 if s % 2 else 0.0)) for s in range(4)]
            + [("bench", program_history(records(80, 4, "stale_read")))])


@pytest.mark.parametrize("linearizable", [False, True],
                         ids=["no-linearizable", "linearizable"])
@pytest.mark.parametrize("sequential", [False, True],
                         ids=["no-sequential", "sequential"])
@pytest.mark.parametrize("name,h", pass_histories(),
                         ids=[n for n, _ in pass_histories()])
def test_two_halves_give_the_one_piece_pass(name, h, sequential,
                                            linearizable):
    old, vg = one_piece_analyze(h, sequential, linearizable)
    d = rw_register.dependencies(h, sequential, linearizable)
    new = rw_register.analysis_of(d)
    assert edges_of(new.graph) == edges_of(old.graph)
    assert set(new.graph.nodes) == set(old.graph.nodes)
    assert new.count == old.count and new.txn_of == old.txn_of
    # the host anomalies, in their order; a cyclic key's witness may be
    # another cycle of that key's versions
    assert set(new.anomalies) == set(old.anomalies)
    for kind, found in old.anomalies.items():
        if kind != "cyclic-versions":
            assert new.anomalies[kind] == found, kind
    cyclic = new.anomalies.get("cyclic-versions", [])
    assert {c["key"] for c in cyclic} == {
        c["key"] for c in old.anomalies.get("cyclic-versions", [])}
    for c in cyclic:
        cyc = c["versions"]
        assert cyc[0] == cyc[-1] and all(
            y in vg[c["key"]][x] for x, y in zip(cyc, cyc[1:])), cyc
    # the version edges are the one-piece pass's
    vid = {kv: i for i, kv in enumerate(d.versions)}
    assert set(zip(d.version_from.tolist(), d.version_to.tolist())) == {
        (vid[(k, x)], vid[(k, y)]) for k, adj in vg.items()
        for x, ys in adj.items() for y in ys}
    assert len(d.version_from) == sum(len(ys) for adj in vg.values()
                                      for ys in adj.values())
    # the whole check on each side: the verdict, and the types that do not
    # hang on the order a witness search meets the edges in (the graph is
    # the same; its edges arrive in another order).  The realtime layer is
    # add_realtime_edges' on both sides.
    got = rw_register.check(h, sequential_keys=sequential,
                            linearizable_keys=linearizable)
    collect_cycle_anomalies(old.graph, old.txn_of, old.anomalies)
    want = finish_result(old.anomalies, ("serializable",), old.count)
    assert (got["valid"], ref.decided(got["anomaly-types"])) == \
        (want["valid"], ref.decided(want["anomaly-types"]))
    # the device path's encoding: one cell a pair of each kind
    enc = elle_tpu.encode(h, "rw-register", sequential_keys=sequential,
                          linearizable_keys=linearizable)
    cells = {(int(s), int(t), k) for k in range(3)
             for s, t in zip(enc.src[k], enc.dst[k]) if s >= 0}
    assert cells == {(a, b, ("ww", "wr", "rw").index(k))
                     for a, b, k in edges_of(new.graph)}
    assert enc.finish_analysis() is enc.analysis


@pytest.mark.parametrize("variant", VARIANTS, ids=lambda v: v or "clean")
def test_only_a_lane_with_something_to_show_builds_the_graph(variant,
                                                            monkeypatch):
    """A lane the device proved acyclic and valid never makes the graph
    object; a refuted one makes it once, for the witness search and the
    artifacts' edge list, which is the host checker's."""
    built = []
    whole = Analysis.graph

    def counted(self):
        built.append(self.count)
        return whole.func(self)
    prop = cached_property(counted)
    prop.__set_name__(Analysis, "graph")
    monkeypatch.setattr(Analysis, "graph", prop)
    h = program_history(records(120, 1, variant))
    res, = elle_tpu.check_batch([h], workload="rw-register", realtime=True,
                                linearizable_keys=True, n_pad_floor=N_PAD)
    if variant is None:
        assert res["valid"] is True and built == []
        assert "edges-full" not in res
        return
    assert res["valid"] is False and built == [res["count"]]
    cpu = rw_register.check(h, realtime=True, linearizable_keys=True)
    assert {(a, b, tuple(k)) for a, b, k in res["edges-full"]} == \
        {(a, b, tuple(k)) for a, b, k in cpu["edges-full"]}


def test_corner_history_holds_every_host_anomaly():
    a = rw_register.analyze(corner_history())
    assert set(a.anomalies) == {"G1a", "G1b", "duplicate-writes",
                                "cyclic-versions"}
    keys = {c["key"] for c in a.anomalies["cyclic-versions"]}
    assert keys == {"a", "q"}
    q = next(c for c in a.anomalies["cyclic-versions"] if c["key"] == "q")
    assert q["versions"] == [5, 5]


def test_an_empty_history_has_no_dependencies():
    d = rw_register.dependencies(History([]), True, True)
    assert d.count == 0 and len(d.version_from) == 0 and len(d.edges) == 0
    assert elle_tpu.check(History([]), workload="rw-register")["valid"]


# -- linearizable keys follow the models ---------------------------------------

@pytest.mark.parametrize("models,strict", [
    (None, False), (("serializable",), False),
    (("snapshot-isolation",), False), (STRICT, True),
    (("strict-1sr",), True), (("PL-SS", "serializable"), True)])
def test_linearizable_keys_follow_the_models(models, strict):
    checker = cycle.wr_workload(consistency_models=models)["checker"]
    assert checker.workload_kw == {"sequential_keys": False,
                                   "linearizable_keys": strict}
    assert checker.realtime is strict
    spec = resolve_checker({"name": "elle-rw-register",
                            "consistency_models": models})
    assert spec.workload_kw["linearizable_keys"] is strict
    # an explicit choice still stands, either way
    for mine in (True, False):
        assert cycle.wr_workload(consistency_models=models,
                                 linearizable_keys=mine)[
            "checker"].workload_kw["linearizable_keys"] is mine
    # no models named: the engine judges strict-serializable exactly when
    # the realtime order is asked for
    assert ElleRwRegister(realtime=True).workload_kw[
        "linearizable_keys"] is True
    assert "linearizable_keys" not in cycle.append_workload(
        consistency_models=models)["checker"].workload_kw


# -- the generator --------------------------------------------------------------

def test_the_register_run_is_the_append_run_with_another_workload():
    lists = la.list_append_history(200, key_count=3, max_writes_per_key=8,
                                   seed=5)
    regs = wr.to_register(lists)
    assert len(regs) == len(lists)
    for a, b in zip(lists, regs):
        assert (a.process, a.type, a.time) == (b.process, b.type, b.time)
        for (f, k, v), (g, kk, x) in zip(a.value, b.value):
            assert kk == k
            if f == "append":
                assert (g, x) == ("w", v)
            else:
                assert g == "r" and x == (v[-1] if v else None)
    assert ref.check(regs)["valid"]


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
    e1, e2 = (elle_tpu.encode(program_history(g["records"]), "rw-register",
                              linearizable_keys=True) for g in (one, other))
    for name in ("src", "dst", "invoke", "complete"):
        assert (getattr(e1, name) == getattr(e2, name)).all(), name
    assert ref.check(one["records"])["valid"]


@pytest.mark.parametrize("name", sorted(wr.CORRUPTORS))
def test_each_corruptor_breaks_one_read(name):
    recs = records(120, 1)
    bad = wr.CORRUPTORS[name](recs, random.Random(3))
    changed = [(a, b) for a, b in zip(recs, bad) if a != b]
    assert len(changed) == 1
    (a, b), = changed
    assert a.type == b.type == OK and len(a.value) == len(b.value)
    assert sum(x != y for x, y in zip(a.value, b.value)) == 1
    assert bad == wr.CORRUPTORS[name](recs, random.Random(3))


# -- the span -------------------------------------------------------

def test_the_version_order_has_a_span_under_the_analysis(rec):
    h = program_history(records(50, 7))
    res = core.analyze({"checker": cycle.wr_workload(
        consistency_models=STRICT)["checker"]}, h)
    assert res["valid"] is True and res["analyzer"] == "elle-tpu"
    evs = rec.snapshot()
    by = {}
    for e in evs:
        by.setdefault(e["name"], []).append(e)
    versions, = by["elle.versions"]
    analyze, = by["elle.analyze"]
    assert versions["parent-span-id"] == analyze["span-id"]
    assert analyze["args"] == {"lanes": 1, "workload": "rw-register"}
    assert versions["args"] == {
        "txns": res["count"], "keys": len({k for o in h for _, k, _ in
                                           o.value}),
        "sequential": False, "linearizable": True}


# -- the cell ------------------------------------------------------------------------

def fake_chip(chips):
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def toy_cell():
    cell = Cell(CELL)
    cell.config.update(txns=200, key_count=3, max_writes_per_key=16)
    return cell


def test_toy_run_is_correct_and_reports_the_version_order(capsys):
    rc = offline_elle.run(toy_cell(), 2**31 + 11, 0.2, True,
                          time.monotonic(), report.Log(),
                          require_chip=fake_chip)
    out, err = capsys.readouterr()
    assert rc == 0 and err.splitlines()[-1].startswith("compared ")
    line = json.loads(out.splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in line["compared"].values())
    assert line["metrics"]["elle.version_order_s"]["value"] > 0
    assert line["metrics"]["entry.host_answers"]["value"] == 0
    assert "elle.host_pass_s" in line["metrics"]
    for name, flag in (("stale_read", "'g-single': True"),
                       ("future_read", "'g1c': True")):
        said = next(x for x in out.splitlines() if f"probe {name}:" in x)
        mine, theirs = said.split("reference:")
        assert "'cyclic': True" in mine and flag in mine
        assert "valid True" not in said and "analyzer elle-tpu" in mine


def test_a_program_without_the_two_halves_exits_before_anything():
    cell = toy_cell()
    assert cell.traffic["requires"] == [
        "jepsen_tpu.elle.rw_register:dependencies"]
    cell.traffic["requires"] = ["jepsen_tpu.elle.rw_register:no_such_half"]
    called = []
    with pytest.raises(offline_requires.Lacking) as e:
        offline_elle.run(cell, 1, 0.2, False, time.monotonic(), report.Log(),
                         require_chip=lambda chips: called.append(chips))
    assert e.value.code and called == []


@pytest.mark.parametrize("control", ["serializable-reference",
                                     "serializable-program"])
def test_the_nearest_weaker_model_is_not_correct(control):
    """``correct`` tells the cell's model from the nearest weaker one: the
    reference, or the program's checker, judging serializability (no
    realtime order, no linearizable keys) answers the probes otherwise."""
    recs = records(250, 3)
    cell = toy_cell()
    pairs = []
    for name in cell.traffic["probes"]["corruptors"]:
        bad = wr.CORRUPTORS[name](recs, random.Random(40))
        want = ref.check(bad, realtime=True)
        if control == "serializable-reference":
            weak = ref.check(bad, realtime=False)
            got = {"valid": weak["valid"],
                   "anomaly-types": weak["anomaly_types"],
                   "device-flags": weak["flags"], "count": weak["count"],
                   "analyzer": "elle-tpu"}
        else:
            got = cycle.wr_workload(consistency_models=("serializable",))[
                "checker"].check({"name": "t"}, program_history(bad))
        pairs.append((got, want))
    verdict = offline_elle.compare([], ref.check(recs), None, ["elle-tpu"],
                                   ref.decided, pairs)
    wrong = {k for k, c in verdict["compared"].items() if not c["ok"]}
    assert {"verdict_mismatches", "flag_mismatches"} <= wrong
    assert not wrong & {"unknown_verdicts", "host_answers",
                        "txn_count_drift"}


def test_the_cells_files_load_through_the_manifest():
    cell = Cell(CELL)
    assert cell.chips == 1
    assert (cell.entry["config"], cell.entry["traffic"]) == (
        "elle-wr-10k", "offline-elle-wr")
    assert {m["name"] for m in cell.end_to_end()} == {"verdict_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer()} == {
        "elle.host_pass_s", "elle.readback_wait_share",
        "kernels.closure_mxu_share", "kernels.closure_rounds_share",
        "elle.version_order_s", "entry.host_answers", "device.idle_share",
        "device.peak_hbm_bytes", "drivers.launches_per_call",
        "compile.window_compiles", "compile.setup_cache_misses",
        "setup.warmup_excess_s", "compile.trace_s", "compile.lower_s",
        "compile.load_s", "compile.eager_s", "setup.warmup_unnamed_s"}
    mine = next(m for m in cell.per_layer()
                if m["name"] == "elle.version_order_s")
    assert (mine["reader"], mine["args"], mine["layer"], mine["moves"]) == (
        "program_span_sum", {"spans": ["elle.versions"],
                             "what": "s_per_call"}, "host prepare",
        "verdict_s")
    # a program from before the span reads nothing
    assert plugin("readers", "program_span_sum", "read")(
        {"trace": None}, **mine["args"]) is None
    config, traffic = cell.config, cell.traffic
    entry = next(c for c in cell.manifest["configs"]
                 if c["name"] == "elle-wr-10k")
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == config["reduced"] == []
    append = Cell("elle-append10k.offline").config
    for key in ("txns", "concurrency", "key_count", "key_dist",
                "key_dist_base", "max_writes_per_key", "min_txn_length",
                "max_txn_length", "read_p", "consistency_models",
                "device_analyzers"):
        assert config[key] == append[key], key
    assert (config["workload"], config["reference"]) == (
        "rw-register", "elle_rw_register")
    assert traffic["loop"] == "offline_elle"
    assert set(traffic["probes"]["corruptors"]) == set(wr.CORRUPTORS)
    checker = offline_elle.program_checker(traffic["entry"],
                                           config["consistency_models"])
    assert isinstance(checker, ElleRwRegister) and checker.realtime
    assert checker.workload_kw["linearizable_keys"] is True
    assert plugin("reference", config["reference"], "decided") is ref.decided
    for w in cell.manifest["workloads"]:
        assert len(w["why"]) <= 200
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        assert len(f.read()) < 64 * 1024
