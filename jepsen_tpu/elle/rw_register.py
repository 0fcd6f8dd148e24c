"""Read/write-register anomaly inference.

Parity: elle.rw-register as consumed by the reference
(jepsen/src/jepsen/tests/cycle/wr.clj:9-25).  Transactions carry
``["w", k, v]`` (v unique per key) and ``["r", k, v]`` mops.  Unlike
list-append, reads don't trace version history, so a per-key *version
order* must be recovered first, from several sources (each an explicit
"must precede" constraint on versions of one key):

- ``initial``  — the initial state ``None`` precedes every written value;
- ``wfr``      — a txn that read v and then wrote v' orders v < v';
- ``ww-txn``   — a txn that wrote v then v' to the same key orders v < v'
  (v is then an *intermediate* version: reads of it by others are G1b);
- ``sequential`` (opt-in ``sequential_keys``) — consecutive writes to a key
  by one process order their values (per-key sequential consistency
  assumption, elle's :sequential-keys?);
- ``linearizable`` (opt-in ``linearizable_keys``) — a write completed
  before another write's invocation orders their values (per-key
  linearizability assumption, elle's :linearizable-keys?).

A cycle in a key's version graph is itself reported (``cyclic-versions``).
The transaction dependency graph then gets:

- wr edges (exact): the unique writer of an observed value → the reader;
- ww edges: writer of v → writer of v' for each version edge v < v';
- rw edges: reader of v → writer of v' for each version edge v < v'
  (sound for serialization cycles: a reader of v must precede the
  installer of any later version);
- realtime edges in strict mode.

Plus G1a (reads of failed writes), G1b (reads of intermediate writes) and
duplicate-write detection.
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from jepsen_tpu.elle.graph import Graph, SearchBudget, edge_list
from jepsen_tpu.elle.list_append import (Analysis, add_realtime_edges,
                                         collect_cycle_anomalies,
                                         finish_result)
from jepsen_tpu.history import FAIL, History, INFO, OK, Op
from jepsen_tpu.txn import READ_FS, WRITE_FS


def check(history: History, realtime: bool = False,
          consistency_models: Optional[Sequence[str]] = None,
          sequential_keys: bool = False,
          linearizable_keys: bool = False,
          search_budget: Optional[SearchBudget] = None) -> Dict[str, Any]:
    """Analyze an rw-register history; ``consistency_models`` selects what
    ``valid`` means (wr.clj:9-25 consumes elle the same way) — see
    :func:`jepsen_tpu.elle.list_append.check`."""
    if consistency_models is None:
        consistency_models = (("strict-serializable",) if realtime
                              else ("serializable",))
    a = analyze(history, sequential_keys=sequential_keys,
                linearizable_keys=linearizable_keys)
    if realtime:
        add_realtime_edges(a.graph, a.oks, a.pairs, budget=search_budget)
    truncated = collect_cycle_anomalies(a.graph, a.txn_of, a.anomalies,
                                        budget=search_budget)
    res = finish_result(a.anomalies, consistency_models, a.count,
                        truncated=truncated)
    res["edges-full"] = edge_list(a.graph)
    return res


def analyze(history: History, sequential_keys: bool = False,
            linearizable_keys: bool = False) -> Analysis:
    """The linear host pass: version-graph recovery, host anomalies, and
    the ww/wr/rw dependency graph — everything but cycle search and the
    realtime layer (see :class:`jepsen_tpu.elle.list_append.Analysis`)."""
    # Client ops only (see list_append.check: nemesis values are not txns).
    history = history.client_ops()
    pairs = history.pair_index()
    oks: List[Tuple[int, Op]] = []
    failed_writes: Set[Tuple[Any, Any]] = set()
    for i, op in enumerate(history):
        if not isinstance(op.value, (list, tuple)):
            continue
        if op.type == OK:
            oks.append((i, op))
        elif op.type == FAIL:
            j = pairs[i]
            txn = op.value or (history[j].value if j >= 0 else None)
            if txn:
                for f, k, v in txn:
                    if f in WRITE_FS:
                        failed_writes.add((k, v))

    anomalies: Dict[str, List[Any]] = defaultdict(list)
    writer: Dict[Tuple[Any, Any], int] = {}
    txn_of: Dict[int, List] = {}
    # intermediate versions: (k, v) overwritten within its own txn (G1b bait)
    intermediate: Dict[Tuple[Any, Any], int] = {}
    for tid, (_, op) in enumerate(oks):
        txn_of[tid] = op.value
        last_w: Dict[Any, Any] = {}
        for f, k, v in op.value:
            if f in WRITE_FS:
                if (k, v) in writer:
                    anomalies["duplicate-writes"].append({"key": k,
                                                          "value": v})
                writer[(k, v)] = tid
                if k in last_w:
                    intermediate[(k, last_w[k])] = tid
                last_w[k] = v

    # ----- per-key version graphs -----------------------------------------
    # vg[k] : value -> set of successor values (direct "precedes" edges)
    vg: Dict[Any, Dict[Any, Set[Any]]] = defaultdict(lambda: defaultdict(set))
    written_values: Dict[Any, Set[Any]] = defaultdict(set)
    for (k, v) in writer:
        written_values[k].add(v)

    for tid, (_, op) in enumerate(oks):
        reads: Dict[Any, Any] = {}
        last_w: Dict[Any, Any] = {}
        for f, k, v in op.value:
            if f in READ_FS:
                reads[k] = v
            elif f in WRITE_FS:
                if k in last_w:            # ww-txn source
                    vg[k][last_w[k]].add(v)
                elif k in reads:           # wfr source
                    if reads[k] != v:
                        vg[k][reads[k]].add(v)
                last_w[k] = v

    for k, vs in written_values.items():   # initial source
        for v in vs:
            if v is not None:              # a written None is not the initial
                vg[k][None].add(v)         # version; avoid a None self-loop

    if sequential_keys or linearizable_keys:
        _order_writes(oks, pairs, vg, sequential_keys, linearizable_keys)

    for k, adj in vg.items():
        cyc = _version_cycle(adj)
        if cyc:
            anomalies["cyclic-versions"].append({"key": k, "versions": cyc})

    # ----- transaction dependency graph -----------------------------------
    g = Graph()
    for tid in range(len(oks)):
        g.add_node(tid)

    # readers[(k, v)] -> tids that externally observed v for k
    readers: Dict[Tuple[Any, Any], List[int]] = defaultdict(list)
    for tid, (_, op) in enumerate(oks):
        seen_w: Set[Any] = set()
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
                if v is not None:
                    w = writer.get((k, v))
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

    return Analysis(graph=g, txn_of=txn_of, anomalies=anomalies,
                    oks=oks, pairs=pairs)


def _order_writes(oks, pairs, vg, sequential_keys, linearizable_keys) -> None:
    """Add per-key version edges from per-process (sequential) and realtime
    (linearizable) order of the writing transactions."""
    # (k -> [(invoke_idx, complete_idx, process, last value written)])
    writes: Dict[Any, List[Tuple[int, int, Any, Any]]] = defaultdict(list)
    for tid, (i, op) in enumerate(oks):
        inv = pairs[i] if pairs[i] >= 0 else i
        last_w: Dict[Any, Any] = {}
        for f, k, v in op.value:
            if f in WRITE_FS:
                last_w[k] = v
        for k, v in last_w.items():
            writes[k].append((min(i, inv), max(i, inv), op.process, v))
    for k, ws in writes.items():
        if sequential_keys:
            by_proc: Dict[Any, List] = defaultdict(list)
            for w in ws:
                by_proc[w[2]].append(w)
            for plist in by_proc.values():
                plist.sort(key=lambda w: w[0])
                for a, b in zip(plist, plist[1:]):
                    if a[3] != b[3]:
                        vg[k][a[3]].add(b[3])
        if linearizable_keys:
            # Realtime order is an interval order; emit a sparse edge set
            # whose transitive closure equals it (full all-pairs would be
            # O(n^2) edges): link a only to successors invoked no later
            # than the earliest completion among a's successors — every
            # other pair is implied through that earliest-completing write.
            ws_sorted = sorted(ws, key=lambda w: w[0])
            n = len(ws_sorted)
            # suffix-min of completion index over ws_sorted[i:]
            suf_min = [0] * (n + 1)
            suf_min[n] = float("inf")
            for i in range(n - 1, -1, -1):
                suf_min[i] = min(ws_sorted[i][1], suf_min[i + 1])
            invokes = [w[0] for w in ws_sorted]
            for a in ws_sorted:
                j = bisect.bisect_right(invokes, a[1])
                if j >= n:
                    continue
                cutoff = suf_min[j]
                for b in ws_sorted[j:]:
                    if b[0] > cutoff:
                        break
                    if a[3] != b[3]:
                        vg[k][a[3]].add(b[3])


def _version_cycle(adj: Dict[Any, Set[Any]]) -> Optional[List[Any]]:
    """Iterative DFS cycle detection over one key's version graph
    (version chains can be as long as the history)."""
    WHITE, GREY, BLACK = 0, 1, 2
    color: Dict[Any, int] = defaultdict(int)
    for root in list(adj):
        if color[root] != WHITE:
            continue
        # stack of (node, iterator over successors); path mirrors the greys
        path: List[Any] = []
        stack = [(root, iter(adj.get(root, ())))]
        color[root] = GREY
        path.append(root)
        while stack:
            v, it = stack[-1]
            advanced = False
            for u in it:
                if color[u] == GREY:
                    return path[path.index(u):] + [u]
                if color[u] == WHITE:
                    color[u] = GREY
                    path.append(u)
                    stack.append((u, iter(adj.get(u, ()))))
                    advanced = True
                    break
            if not advanced:
                color[v] = BLACK
                path.pop()
                stack.pop()
    return None
