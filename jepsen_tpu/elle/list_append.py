"""List-append anomaly inference.

Parity: elle.list-append as consumed by the reference
(jepsen/src/jepsen/tests/cycle/append.clj:11-46).  The workload: each
transaction is a list of mops ``["append", k, v]`` / ``["r", k, [v...]]``
where appended values are unique per key.  Reads observe the key's whole
list, which *traces the version history exactly* — that's what makes
dependency inference sound:

- version order per key = the longest read list (all reads must agree on
  prefixes; disagreement = :incompatible-order);
- wr edge  W →wr R:  R read a list whose last element was appended by W;
- ww edge  W1 →ww W2: W2 appended the value immediately following W1's in
  the version order;
- rw edge  R →rw W:  R observed the state just before W's append;
- realtime edge T1 → T2: T1's ok preceded T2's invoke (strict mode).

Anomalies: G1a (read of aborted write), G1b (read of intermediate state),
duplicates, incompatible orders, and dependency cycles classified as
G0 (ww only), G1c (ww+wr), G-single (exactly one rw), G2-item (≥1 rw).
"""

from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field
from functools import cached_property
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from jepsen_tpu.elle import consistency
from jepsen_tpu.elle.graph import (Graph, SearchBudget, cycle_edge_kinds,
                                   edge_list, gsingle_cycles,
                                   nonadjacent_rw_cycles, peeled_cycles)
from jepsen_tpu.history import FAIL, History, INFO, INVOKE, OK, Op

CYCLE_SEVERITY = ["G0", "G1c", "G-single", "G-nonadjacent", "G2-item"]

#: the dependency kinds a host pass infers; ``Dependencies.edges`` names a
#: kind by its place here
EDGE_KINDS = ("ww", "wr", "rw")
WW, WR, RW = range(3)

# Same sentinel as checker.core.UNKNOWN — spelled out so elle stays
# importable without the checker package.
UNKNOWN = "unknown"


def classify_cycle(kind_sets: List[Set[str]]) -> str:
    """Label a cycle by the *weakest-model-refuting* reading of its edges:
    an edge offering a non-rw kind is read as non-rw (fewer anti-dependency
    edges refute weaker models), and edges closable only in realtime push
    the label to its ``-realtime`` variant (refutes only the strict tier).

    G0 all-ww < G1c ww+wr < G-single (one forced rw) < G-nonadjacent
    (>= 2 forced rw, none cyclically adjacent — the un-SI-able shape) <
    G2-item (>= 2 forced rw, some adjacent — SI-legal write skew)."""
    rt_needed = any(ks == {"realtime"} for ks in kind_sets)
    core = [ks - {"realtime"} for ks in kind_sets]
    rw_pos = [i for i, ks in enumerate(core) if ks == {"rw"}]
    n = len(core)
    if not rw_pos:
        if all((not ks) or ("ww" in ks) for ks in core):
            label = "G0"
        else:
            label = "G1c"
    elif len(rw_pos) == 1:
        label = "G-single"
    else:
        adjacent = any((j - i) % n == 1
                       for i in rw_pos for j in rw_pos if i != j)
        label = "G2-item" if adjacent else "G-nonadjacent"
    return label + ("-realtime" if rt_needed else "")


def _cycle_sig(cyc: List[int]) -> Tuple[int, ...]:
    """Rotation-normalized signature of a cycle [n0, ..., n0]."""
    body = tuple(cyc[:-1])
    k = body.index(min(body))
    return body[k:] + body[:k]


def collect_cycle_anomalies(g: Graph, txn_of: Dict[int, List],
                            anomalies: Dict[str, List[Any]],
                            budget: Optional[SearchBudget] = None) -> bool:
    """Run the full cycle-search suite and file each distinct cycle under
    its label.  The generic peeled pass alone is not enough below
    serializability: one SCC can hide a G-single or G-nonadjacent witness
    behind a shorter SI-legal cycle, so each anomaly family gets its own
    targeted search (elle searches per anomaly type the same way):

    - ww subgraph          -> G0
    - ww+wr subgraph       -> G1c (its all-ww cycles dedup into G0)
    - one-rw return paths  -> G-single
    - nonadjacent-rw BFS   -> G-nonadjacent
    - full graph, peeled   -> G2-item and anything the above missed

    ``budget`` (one :class:`SearchBudget` shared by all five searches)
    bounds the work; returns True when the suite was truncated — the
    caller must then degrade a clean verdict (see :func:`finish_result`).
    """
    searches = [
        peeled_cycles(g.filter_kinds({"ww", "realtime"}), budget),
        peeled_cycles(g.filter_kinds({"ww", "wr", "realtime"}), budget),
        gsingle_cycles(g, budget=budget),
        nonadjacent_rw_cycles(g, search_budget=budget),
        peeled_cycles(g, budget),
    ]
    seen: Set[Tuple] = set()
    for cycles in searches:
        for cyc in cycles:
            kinds = cycle_edge_kinds(g, cyc)
            label = classify_cycle(kinds)
            key = (label, _cycle_sig(cyc))
            if key in seen:
                continue
            seen.add(key)
            anomalies[label].append({
                "cycle": [txn_of[t] for t in cyc],
                "edges": [sorted(ks) for ks in kinds]})
    return budget is not None and budget.truncated


@dataclass
class Analysis:
    """Everything the linear host pass produces *before* cycle search: the
    dependency graph (ww/wr/rw only — the realtime layer is dense and is
    added on demand via :func:`add_realtime_edges`), per-txn labels, the
    host-detectable anomalies (G1a/G1b/duplicates/incompatible-order), and
    the ok/pair indices the realtime order derives from.  This is the
    shared front half of the CPU checker and the elle_tpu encoder — both
    paths literally analyze the same object, which is what makes their
    anomaly sets identical by construction."""
    txn_of: Dict[int, List]
    anomalies: Dict[str, List[Any]] = field(default_factory=dict)
    oks: List[Tuple[int, Op]] = field(default_factory=list)
    pairs: Sequence[int] = ()
    #: the ww/wr/rw edges as ``Dependencies.edges`` has them: three ints an
    #: edge, the kind as its place in ``EDGE_KINDS``
    edges: Sequence[int] = ()

    @property
    def count(self) -> int:
        return len(self.oks)

    @cached_property
    def graph(self) -> Graph:
        """The edges as a :class:`Graph`, built on first use: a lane the
        device proved acyclic and valid never asks for it."""
        g = Graph()
        for tid in range(self.count):
            g.add_node(tid)
        it = iter(self.edges.tolist() if isinstance(self.edges, np.ndarray)
                  else self.edges)
        for a, b, kind in zip(it, it, it):
            g.add_edge(a, b, EDGE_KINDS[kind])
        return g


def add_realtime_edges(g: Graph, oks: List[Tuple[int, Op]],
                       pairs: Sequence[int],
                       budget: Optional[SearchBudget] = None) -> None:
    """T1 -> T2 iff T1's completion index precedes T2's invocation index
    (strict mode).  O(n^2) and dense — kept out of :func:`analyze` so the
    device engine can compute the same relation as a broadcast compare and
    only materialize these edges for witness recovery.  ``budget``'s
    deadline is asked once a row (no steps are charged): past it the layer
    stays partial and the budget reads ``truncated``, so the search that
    follows stops at once and the verdict degrades as for any cut search
    (at 10,000 transactions the whole layer is 4.5e7 edges)."""
    for t1, (i1, _) in enumerate(oks):
        if budget is not None and not budget.spend(0):
            return
        for t2, (i2, _) in enumerate(oks):
            if t1 == t2:
                continue
            inv2 = pairs[i2]
            if inv2 >= 0 and i1 < inv2:
                g.add_edge(t1, t2, "realtime")


def check(history: History,
          consistency_models: Optional[Sequence[str]] = None,
          realtime: bool = False,
          search_budget: Optional[SearchBudget] = None) -> Dict[str, Any]:
    """Analyze a list-append history; returns an elle-shaped result map.

    ``consistency_models`` selects what ``valid`` means (append.clj:15-21
    parity): all anomalies found are always reported, but only those that
    refute a *requested* model make the history invalid — e.g. a G2-item
    write-skew cycle refutes ``("serializable",)`` (the default) yet passes
    ``("snapshot-isolation",)``.  The result carries elle's weakest-model
    boundary under ``not`` / ``also-not``.  Default: serializable, or
    strict-serializable when ``realtime`` ordering is requested.
    ``search_budget`` bounds cycle recovery (see :class:`SearchBudget`)."""
    if consistency_models is None:
        consistency_models = (("strict-serializable",) if realtime
                              else ("serializable",))
    a = analyze(history)
    if realtime:
        add_realtime_edges(a.graph, a.oks, a.pairs,
                           budget=search_budget)
    truncated = collect_cycle_anomalies(a.graph, a.txn_of, a.anomalies,
                                        budget=search_budget)
    res = finish_result(a.anomalies, consistency_models, a.count,
                        truncated=truncated)
    # complete edge list for artifact rendering; popped by
    # elle.render.write_artifacts alongside anomalies-full
    res["edges-full"] = edge_list(a.graph)
    return res


@dataclass
class Dependencies:
    """The first half of the host pass: the ok transactions, each key's
    version order and the ww/wr/rw edges in the order the graph takes
    them.  It is all a device needs before its closures can start;
    :func:`analysis_of` makes the :class:`Analysis` of it (the host
    anomalies), and may do so while they run."""
    oks: List[Tuple[int, Op]]
    pairs: Sequence[int]
    txn_of: Dict[int, List]
    writer: Dict[Tuple[Any, Any], int]
    failed_writes: Set[Tuple[Any, Any]]
    #: every read of a known list, in history order: (txn, op, key, list)
    reads: List[Tuple[int, Op, Any, List[Any]]]
    duplicates: List[Dict[str, Any]]
    #: three ints an edge, one edge after another: from, to, the kind as
    #: its place in ``EDGE_KINDS`` (flat, so that 45,000 edges are no
    #: 45,000 tuples for the collector to walk); an edge may repeat
    edges: List[int]

    @property
    def count(self) -> int:
        return len(self.oks)


def dependencies(history: History) -> Dependencies:
    """Indices, version orders and the ww/wr/rw dependency edges."""
    # Client ops only: a nemesis op's value (e.g. the killed node list)
    # is not a txn, and elle likewise analyzes the client subhistory
    # (elle's history preparation removes non-txn ops).
    history = history.client_ops()
    oks: List[Tuple[int, Op]] = []
    failed_writes: Set[Tuple[Any, Any]] = set()
    info_writes: Set[Tuple[Any, Any]] = set()
    pairs = history.pair_index()

    for i, op in enumerate(history):
        if not isinstance(op.value, (list, tuple)):
            continue
        if op.type == OK:
            oks.append((i, op))
        elif op.type in (FAIL, INFO):
            j = pairs[i]
            txn = op.value if op.value else (
                history[j].value if j >= 0 else None)
            if txn:
                for f, k, v in txn:
                    if f == "append":
                        (failed_writes if op.type == FAIL
                         else info_writes).add((k, v))

    # writer index + duplicate detection, the reads, per-key longest read
    writer: Dict[Tuple[Any, Any], int] = {}
    txn_of: Dict[int, List] = {}
    duplicates: List[Dict[str, Any]] = []
    reads: List[Tuple[int, Op, Any, List[Any]]] = []
    longest: Dict[Any, List[Any]] = {}
    for tid, (_, op) in enumerate(oks):
        txn_of[tid] = op.value
        for f, k, v in op.value:
            if f == "append":
                if (k, v) in writer:
                    duplicates.append({"key": k, "value": v})
                writer[(k, v)] = tid
            elif f in ("r", "read") and v is not None:
                lst = v if type(v) is list else list(v)
                reads.append((tid, op, k, lst))
                if len(lst) > len(longest.get(k, ())):
                    longest[k] = lst

    # Values appended but never observed by any read still have a sound
    # place in the (append-only) version order: had such an append preceded
    # the state some read observed, the value would appear in that read, so
    # every unobserved append follows the longest observed list — giving ww
    # edges from the last observed writer and rw edges from every reader
    # (this is what makes pure write skew — two reads of [] and two blind
    # appends — a detectable G2-item cycle).
    by_key: Dict[Any, List[Any]] = defaultdict(list)
    for (k, v) in writer:
        by_key[k].append(v)
    unobserved: Dict[Any, List[Any]] = {}
    for k, vs in by_key.items():
        obs = set(longest.get(k, []))
        late = [v for v in vs if v not in obs]
        if late:
            unobserved[k] = late

    edges: List[int] = []
    for k, order in longest.items():
        # ww edges along the version order
        ws = [writer.get((k, v)) for v in order]
        for wa, wb in zip(ws, ws[1:]):
            if wa is not None and wb is not None and wa != wb:
                edges += (wa, wb, WW)
        if order:
            wa = ws[-1]
            for v in unobserved.get(k, ()):
                wb = writer.get((k, v))
                if wa is not None and wb is not None and wa != wb:
                    edges += (wa, wb, WW)

    for rtid, _, k, lst in reads:
        if lst:
            w = writer.get((k, lst[-1]))
            if w is not None and w != rtid:
                edges += (w, rtid, WR)
        # rw: the next value after the observed state
        order = longest.get(k, [])
        n = len(lst)
        if n < len(order) and order[:n] == lst and order[n] is not None:
            w = writer.get((k, order[n]))
            if w is not None and w != rtid:
                edges += (rtid, w, RW)
        # rw: every unobserved append to k follows any observed state
        late = unobserved.get(k)
        if late:
            observed = set(lst)
            for v in late:
                if v in observed:
                    continue
                w = writer.get((k, v))
                if w is not None and w != rtid:
                    edges += (rtid, w, RW)

    return Dependencies(oks=oks, pairs=pairs, txn_of=txn_of, writer=writer,
                        failed_writes=failed_writes, reads=reads,
                        duplicates=duplicates, edges=edges)


def analysis_of(d: Dependencies) -> Analysis:
    """The second half of the host pass: the host anomalies (duplicates,
    G1a, incompatible orders, G1b); the graph follows from the edges on
    first use (:attr:`Analysis.graph`)."""
    anomalies: Dict[str, List[Any]] = defaultdict(list)
    if d.duplicates:
        anomalies["duplicate-appends"].extend(d.duplicates)

    # prefix consistency against the longest read so far + G1a
    failed_writes = d.failed_writes
    longest: Dict[Any, List[Any]] = {}
    for _, op, k, lst in d.reads:
        # G1a: observed value appended by a failed txn
        for x in lst:
            if (k, x) in failed_writes:
                anomalies["G1a"].append({"key": k, "value": x,
                                         "reader": op.to_dict()})
        cur = longest.get(k, [])
        short, long_ = (lst, cur) if len(lst) <= len(cur) else (cur, lst)
        if short != long_[:len(short)]:
            anomalies["incompatible-order"].append(
                {"key": k, "a": cur, "b": lst})
        if len(lst) > len(cur):
            longest[k] = lst

    # G1b: a read that ends inside another txn's append run
    # (observes some but not all of a txn's appends to k, with nothing after)
    appends_by_txn_key: Dict[Tuple[int, Any], List[Any]] = defaultdict(list)
    for tid, (_, op) in enumerate(d.oks):
        for f, k, v in op.value:
            if f == "append":
                appends_by_txn_key[(tid, k)].append(v)
    for rtid, op, k, lst in d.reads:
        if not lst:
            continue
        last = lst[-1]
        wtid = d.writer.get((k, last))
        if wtid is None or wtid == rtid:
            continue
        run = appends_by_txn_key[(wtid, k)]
        if run and last != run[-1]:
            anomalies["G1b"].append({"key": k, "value": last,
                                     "reader": op.to_dict()})

    return Analysis(txn_of=d.txn_of, anomalies=anomalies, oks=d.oks,
                    pairs=d.pairs, edges=d.edges)


def analyze(history: History) -> Analysis:
    """The linear host pass: indices, version orders, host anomalies, and
    the ww/wr/rw dependency graph — everything but cycle search and the
    realtime layer."""
    return analysis_of(dependencies(history))


def finish_result(anomalies: Dict[str, List[Any]],
                  consistency_models: Sequence[str],
                  count: int, truncated: bool = False) -> Dict[str, Any]:
    """Shared result assembly: model-relative validity + boundary report.

    ``truncated`` (cycle search hit its :class:`SearchBudget`) degrades a
    *clean* verdict to unknown — an exhausted search may simply not have
    reached the refuting cycle — while found anomalies still refute.  The
    marker rides as its own ``cycle-search-truncated`` key, never as an
    anomaly type: consistency.refuted_models treats unknown anomaly types
    as refuting everything, which would turn "gave up" into "invalid"."""
    valid = consistency.judge(consistency_models, anomalies)
    if truncated and valid is True:
        valid = UNKNOWN
    res = {"valid": valid,
           "consistency-models": [consistency.canonicalize(m)
                                  for m in consistency_models],
           **consistency.boundary(anomalies),
           "anomaly-types": sorted(anomalies),
           "anomalies": {k: v[:8] for k, v in anomalies.items()},
           # complete map for artifact rendering; popped by
           # elle.render.write_artifacts so results stay small
           "anomalies-full": dict(anomalies),
           "count": count}
    if truncated:
        res["cycle-search-truncated"] = True
    return res
