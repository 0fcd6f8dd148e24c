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
  linearizability assumption, elle's :linearizable-keys?).  Strict
  serializability implies it, so ``checker.elle`` turns it on wherever a
  strict model is asked for, unless the caller says otherwise.

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

The pass comes in two halves, as list-append's does.  ``dependencies``
flattens the ok transactions' micro-ops into arrays once and infers the
version orders and the ww/wr/rw edges over them in numpy: all a device
needs before its closures can start.  ``analysis_of`` makes the
:class:`Analysis` of that (the host anomalies) and may run while the
closures do; the graph as an object is built from the edges only where
it is asked for (a cycle search on the host).
"""

from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from jepsen_tpu.elle.graph import SearchBudget, edge_list
from jepsen_tpu.elle.list_append import (RW, WR, WW, Analysis,
                                         add_realtime_edges,
                                         collect_cycle_anomalies,
                                         finish_result)
from jepsen_tpu.history import FAIL, INFO, INVOKE, NEMESIS, OK, History, Op
from jepsen_tpu.obs.recorder import span
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


@dataclass
class Dependencies:
    """The first half of the host pass: the ok transactions, each key's
    version order and the ww/wr/rw edges, as arrays.  A *version* is one
    (key, value) pair, numbered in the order the ok transactions first
    name it; ``versions[i]`` is version ``i``'s pair."""
    oks: List[Tuple[int, Op]]
    pairs: Sequence[int]
    versions: List[Tuple[Any, Any]]
    #: [V] the version was written by a failed transaction
    failed: np.ndarray
    #: [V] the last ok transaction that wrote the version and then another
    #: value of its key (so the version is intermediate), or -1
    intermediate: np.ndarray
    #: versions written again, once a repeat, in micro-op order
    duplicates: np.ndarray
    #: every external read (no write of its key before it in its own
    #: transaction), in history order: the reader, the version read
    read_txn: np.ndarray
    read_version: np.ndarray
    #: the version orders, every source together, each edge once:
    #: ``version_from[i]`` precedes ``version_to[i]`` (same key)
    version_from: np.ndarray
    version_to: np.ndarray
    #: [V] a number every version edge of a valid history goes up in: the
    #: place of the version's last write among the ok transactions'
    #: micro-ops, which are in completion order (-1 where no ok transaction
    #: wrote it); only a key with an edge that does not go up can hold a
    #: cycle, so only such a key's versions are searched for one
    rank: np.ndarray
    #: three ints an edge, one edge after another: from, to, the kind as
    #: its place in ``EDGE_KINDS``; an edge may repeat
    edges: np.ndarray

    @property
    def count(self) -> int:
        return len(self.oks)


def _runs(g: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Where each run of equal values of ``g`` starts, and where it ends."""
    change = g[1:] != g[:-1]
    return np.r_[True, change][:len(g)], np.r_[change, True][:len(g)]


def _last_before(mask: np.ndarray, start: np.ndarray) -> np.ndarray:
    """For each position, the last earlier position of its own group (the
    group starts at ``start``) where ``mask`` holds; -1 where none does."""
    upto = np.maximum.accumulate(np.where(mask, np.arange(len(mask)), -1))
    prev = np.r_[-1, upto[:-1]] if len(mask) else upto
    return np.where(prev >= start, prev, -1)


def _ranges(starts: np.ndarray, counts: np.ndarray) -> Tuple[np.ndarray,
                                                             np.ndarray]:
    """For each ``i``, ``counts[i]`` entries: ``(i, starts[i] + 0, 1, ...)``
    flattened, as two arrays."""
    which = np.repeat(np.arange(len(counts)), counts)
    first = np.cumsum(counts) - counts
    return which, starts[which] + np.arange(len(which)) - first[which]


def dependencies(history: History, sequential_keys: bool = False,
                 linearizable_keys: bool = False) -> Dependencies:
    """The ok transactions, version orders and ww/wr/rw dependency edges."""
    # One pass over the client ops (see list_append.check: nemesis values
    # are not txns), skipping the nemesis's in place: each completion paired
    # with its process's open invocation (``History.pair_index``'s rule), the
    # failed transactions' writes, and the ok transactions' micro-ops
    # flattened: one entry a read or write, its version's number,
    # complemented (negative) for a read.
    ops = history.ops
    pair = [-1] * len(ops)
    open_invokes: Dict[Any, int] = {}
    oks: List[Tuple[int, Op]] = []
    failed_writes: Set[Tuple[Any, Any]] = set()
    vids: Dict[Tuple[Any, Any], int] = {}
    mops: List[int] = []
    ends: List[int] = []
    put, version_of = mops.append, vids.get
    for i, op in enumerate(ops):
        p, t = op.process, op.type
        if p == NEMESIS:
            continue
        if t == INVOKE:
            open_invokes[p] = i
            continue
        if t != OK and t != FAIL and t != INFO:
            continue
        j = open_invokes.pop(p, -1)
        if j >= 0:
            pair[i] = j
            pair[j] = i
        txn = op.value
        if not isinstance(txn, (list, tuple)):
            continue
        if t == OK:
            oks.append((i, op))
            for f, k, v in txn:
                if f in WRITE_FS:
                    vid = version_of((k, v))
                    if vid is None:
                        vid = vids[(k, v)] = len(vids)
                    put(vid)
                elif f in READ_FS:
                    vid = version_of((k, v))
                    if vid is None:
                        vid = vids[(k, v)] = len(vids)
                    put(~vid)
            ends.append(len(mops))
        elif t == FAIL:
            for f, k, v in txn or (ops[j].value if j >= 0 else None) or ():
                if f in WRITE_FS:
                    failed_writes.add((k, v))
    pairs = np.array(pair, np.int64)
    keys: Dict[Any, int] = {}
    version_key = [keys.setdefault(k, len(keys)) for k, _ in vids]
    # each key's initial state is a version too
    initial = np.zeros(len(keys), np.int64)
    for k, kid in keys.items():
        vid = vids.setdefault((k, None), len(version_key))
        if vid == len(version_key):
            version_key.append(kid)
        initial[kid] = vid
    n, n_ver = len(oks), len(vids)
    versions = list(vids)
    vkey = np.array(version_key, np.int64)
    is_none = np.zeros(n_ver, bool)
    is_none[initial] = True
    m = np.array(mops, np.int64)
    write = m >= 0
    ver = np.where(write, m, ~m)
    kid = vkey[ver]
    txn = np.repeat(np.arange(n), np.diff(np.r_[0, ends]).astype(np.int64))
    failed = np.zeros(n_ver, bool)
    for kv in failed_writes:
        vid = vids.get(kv)
        if vid is not None:
            failed[vid] = True

    done = np.fromiter((i for i, _ in oks), np.int64, n)
    inv = pairs[done]
    invoke = np.where(inv >= 0, np.minimum(inv, done), done)
    complete = np.maximum(inv, done)

    writer = np.full(n_ver, -1, np.int64)
    np.maximum.at(writer, ver[write], txn[write])
    rank = np.full(n_ver, -1, np.int64)
    np.maximum.at(rank, ver[write], np.flatnonzero(write))
    w_ver = ver[write]
    first = np.zeros(len(w_ver), bool)
    first[np.unique(w_ver, return_index=True)[1]] = True
    duplicates = w_ver[~first]

    # within a transaction, by key: the micro-ops of one (txn, key) in their
    # order, with the last write and the last read before each
    n_keys = max(1, len(keys))
    order = np.argsort(txn * n_keys + kid, kind="stable")
    s_txn, s_kid, s_ver, s_write = txn[order], kid[order], ver[order], \
        write[order]
    group = s_txn * n_keys + s_kid
    start = np.maximum.accumulate(
        np.where(_runs(group)[0], np.arange(len(group)), 0))
    last_w = _last_before(s_write, start)
    last_r = _last_before(~s_write, start)

    intermediate = np.full(n_ver, -1, np.int64)
    ww_txn = s_write & (last_w >= 0)
    np.maximum.at(intermediate, s_ver[last_w[ww_txn]], s_txn[ww_txn])
    external = np.zeros(len(group), bool)
    external[order] = ~s_write & (last_w < 0)
    read_txn, read_version = txn[external], ver[external]

    with span("elle.versions", txns=n, keys=len(keys),
              sequential=sequential_keys, linearizable=linearizable_keys):
        src: List[np.ndarray] = []
        dst: List[np.ndarray] = []
        # ww-txn: a write after a write of its key in its own transaction
        src.append(s_ver[last_w[ww_txn]])
        dst.append(s_ver[ww_txn])
        # wfr: a first write after a read of its key, of another value
        wfr = s_write & (last_w < 0) & (last_r >= 0)
        wfr[wfr] = s_ver[last_r[wfr]] != s_ver[wfr]
        src.append(s_ver[last_r[wfr]])
        dst.append(s_ver[wfr])
        # initial: the key's initial state before every value written
        written = np.flatnonzero((writer >= 0) & ~is_none)
        src.append(initial[vkey[written]])
        dst.append(written)
        if sequential_keys or linearizable_keys:
            # each ok transaction's last write to each key it writes
            wi = np.flatnonzero(s_write)
            last = wi[_runs(group[wi])[1]]
            w_txn, w_key, w_v = s_txn[last], s_kid[last], s_ver[last]
            if sequential_keys:
                procs: Dict[Any, int] = {}
                proc = np.array([procs.setdefault(oks[t][1].process,
                                                  len(procs))
                                 for t in w_txn.tolist()], np.int64)
                o = np.lexsort((invoke[w_txn], proc, w_key))
                same = ((w_key[o][1:] == w_key[o][:-1])
                        & (proc[o][1:] == proc[o][:-1])
                        & (w_v[o][1:] != w_v[o][:-1]))
                src.append(w_v[o][:-1][same])
                dst.append(w_v[o][1:][same])
            if linearizable_keys:
                a, b = _linearizable(w_key, invoke[w_txn], complete[w_txn],
                                     len(ops) + 1)
                differ = w_v[a] != w_v[b]
                src.append(w_v[a][differ])
                dst.append(w_v[b][differ])
        pair = np.unique(np.concatenate(src) * n_ver + np.concatenate(dst))
        v_from, v_to = pair // max(1, n_ver), pair % max(1, n_ver)

    # the transaction graph: wr from each read's writer, ww and rw along
    # each version edge into a version some ok transaction wrote
    w_read = writer[read_version]
    wr = (w_read >= 0) & (w_read != read_txn) & ~is_none[read_version]
    w_to = writer[v_to]
    into = w_to >= 0
    w_from = writer[v_from]
    ww = into & (w_from >= 0) & (w_from != w_to)
    by_version = np.argsort(read_version, kind="stable")
    counts = np.bincount(read_version, minlength=n_ver)
    starts = np.cumsum(counts) - counts
    e, at = _ranges(starts[v_from[into]], counts[v_from[into]])
    reader = read_txn[by_version[at]]
    target = w_to[into][e]
    rw = reader != target
    edges = np.concatenate([
        np.stack([w_read[wr], read_txn[wr], np.full(wr.sum(), WR)], 1),
        np.stack([w_from[ww], w_to[ww], np.full(ww.sum(), WW)], 1),
        np.stack([reader[rw], target[rw], np.full(rw.sum(), RW)], 1),
    ]).astype(np.int64).reshape(-1)
    return Dependencies(
        oks=oks, pairs=pairs, versions=versions, failed=failed,
        intermediate=intermediate, duplicates=duplicates, read_txn=read_txn,
        read_version=read_version, version_from=v_from, version_to=v_to,
        rank=rank, edges=edges)


def _linearizable(key: np.ndarray, invoke: np.ndarray, complete: np.ndarray,
                  horizon: int) -> Tuple[np.ndarray, np.ndarray]:
    """The writes' realtime order on each key as a sparse edge set whose
    transitive closure equals it (full all-pairs would be O(n^2) edges):
    ``a`` links only to the writes invoked after it completed and no later
    than the earliest completion among those, every other pair being
    implied through that earliest-completing write.  Every key at once:
    times are offset by ``key * horizon`` (past every time given), so each
    key's writes sort apart and no search leaves its key.  Returns
    ``(a, b)`` indices of the writes given."""
    o = np.lexsort((invoke, key))
    base = key[o] * horizon
    inv, comp = base + invoke[o], base + complete[o]
    # earliest completion from each write on, within its key: a later key's
    # times are all larger
    suffix_min = np.minimum.accumulate(comp[::-1])[::-1]
    j = np.searchsorted(inv, comp, side="right")
    ok = j < len(inv)
    ok[ok] = key[o][j[ok]] == key[o][ok]
    end = np.zeros_like(j)
    end[ok] = np.searchsorted(inv, suffix_min[j[ok]], side="right")
    a, b = _ranges(j, np.where(ok, end - j, 0))
    return o[a], o[b]


def analysis_of(d: Dependencies) -> Analysis:
    """The second half of the host pass: the host anomalies
    (duplicate-writes, cyclic-versions, G1a, G1b); the graph follows from
    the edges on first use (``Analysis.graph``)."""
    anomalies: Dict[str, List[Any]] = defaultdict(list)
    for vid in d.duplicates.tolist():
        k, v = d.versions[vid]
        anomalies["duplicate-writes"].append({"key": k, "value": v})

    vg: Dict[Any, Dict[Any, Set[Any]]] = defaultdict(
        lambda: defaultdict(set))
    suspect = d.rank[d.version_from] >= d.rank[d.version_to]
    keys = {d.versions[a][0] for a in d.version_from[suspect].tolist()}
    for a, b in (zip(d.version_from.tolist(), d.version_to.tolist())
                 if keys else ()):
        k, v = d.versions[a]
        if k in keys:
            vg[k][v].add(d.versions[b][1])
    for k, adj in vg.items():
        cyc = _version_cycle(adj)
        if cyc:
            anomalies["cyclic-versions"].append({"key": k, "versions": cyc})

    iw = d.intermediate[d.read_version]
    g1a = d.failed[d.read_version]
    g1b = (iw >= 0) & (iw != d.read_txn)
    for i in np.flatnonzero(g1a | g1b).tolist():
        k, v = d.versions[d.read_version[i]]
        reader = d.oks[d.read_txn[i]][1].to_dict()
        if g1a[i]:
            anomalies["G1a"].append({"key": k, "value": v,
                                     "reader": reader})
        if g1b[i]:
            anomalies["G1b"].append({"key": k, "value": v,
                                     "reader": reader})

    return Analysis(txn_of={t: op.value for t, (_, op) in enumerate(d.oks)},
                    anomalies=anomalies, oks=d.oks, pairs=d.pairs,
                    edges=d.edges)


def analyze(history: History, sequential_keys: bool = False,
            linearizable_keys: bool = False) -> Analysis:
    """The linear host pass: version-graph recovery, host anomalies, and
    the ww/wr/rw dependency graph — everything but cycle search and the
    realtime layer (see :class:`jepsen_tpu.elle.list_append.Analysis`)."""
    return analysis_of(dependencies(history, sequential_keys,
                                    linearizable_keys))


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
