"""A plain reference for the list-append checker: Elle's inference (Kingsbury
& Alvaro, VLDB 2020, section 4 "list-append") over the benchmark's plain
records, and cyclicity by Tarjan's strongly connected components.  Imports
nothing of the program; no matrices, no device, no search for witnesses.

``check(records, realtime=True)`` returns

  valid          no anomaly below and no dependency cycle: serializable,
                 or strict-serializable with ``realtime``
  anomaly_types  the types this reference *decides* (``DECIDED``), sorted
  flags          ``cyclic``, ``g0``, ``g1c``, ``g-single``: is there a cycle
                 in ww+wr+rw, in ww, in ww+wr (each with the realtime order
                 when asked for), and is there an rw edge a -> b with b
                 reaching a without rw
  count          the ok transactions
  unnamed_cycle  there is a cycle and none of the families decided here
                 holds one: every cycle has two anti-dependencies or more

Inference, per key: the version order is the longest read (every read a
prefix of it, else ``incompatible-order``); ww between the appenders of
consecutive elements; wr from the appender of a read's last element to the
reader; rw from a reader to the appender of the element after what it read.
From the records alone: ``G1a`` (a read holds an element whose append
failed), ``G1b`` (a read ends in an element that was not its appender's last
append to that key), ``duplicate-appends``.

Departures from Elle's published inference, each beside its code below:
unobserved appends are ordered after the longest read (D1); crashed
(``info``) transactions are no nodes (D2); no ``internal`` and no
``duplicate-elements`` check (D3); the realtime order is a chain of time
nodes, not an edge per pair (D4); the cycle anomalies named are those that
reachability decides (D5).
"""

from __future__ import annotations

from typing import (Any, Callable, Dict, Iterable, List, Sequence, Set,
                    Tuple)

INVOKE, OK, FAIL = "invoke", "ok", "fail"
WW, WR, RW = "ww", "wr", "rw"
FLAG_NAMES = ("cyclic", "g0", "g1c", "g-single")

#: D5.  The anomaly types this reference names.  Whether a graph holds a
#: *simple* cycle with two or more anti-dependency edges (Elle's G2-item,
#: G-nonadjacent) is the two-disjoint-paths problem, which no plain
#: reachability pass decides and Elle itself searches with a budget: such a
#: history is refuted here by ``cyclic`` alone and its cycles stay unnamed.
#: A ``-realtime`` name is given only where no cycle of that family closes
#: without a realtime edge (the family's plain name refutes more).
HOST_TYPES = ("G1a", "G1b", "duplicate-appends", "incompatible-order")
CYCLE_FAMILIES = ("G0", "G1c", "G-single")
DECIDED = frozenset(HOST_TYPES) | {f + s for f in CYCLE_FAMILIES
                                   for s in ("", "-realtime")}

Edge = Tuple[int, int]


def decided(anomaly_types: Iterable[str]) -> Set[str]:
    """A checker's anomaly types as this reference would name them: those it
    decides, a family's ``-realtime`` name dropped where its plain name
    stands (a search reports every cycle it meets, and beside a plain cycle
    it usually meets one that takes a realtime shortcut)."""
    types = set(anomaly_types) & DECIDED
    return {t for t in types
            if not (t.endswith("-realtime") and t[:-len("-realtime")] in types)}


def sccs(n: int, succ: Sequence[Sequence[int]]) -> List[int]:
    """Tarjan's algorithm, iterative: node -> component number.  Components
    come out in reverse topological order (a component's number is lower
    than that of every component that reaches it)."""
    index = [-1] * n
    low = [0] * n
    comp = [-1] * n
    on_stack = [False] * n
    stack: List[int] = []
    counter = ncomp = 0
    for root in range(n):
        if index[root] >= 0:
            continue
        work = [(root, 0)]
        index[root] = low[root] = counter
        counter += 1
        stack.append(root)
        on_stack[root] = True
        while work:
            v, i = work.pop()
            out = succ[v]
            if i < len(out):
                work.append((v, i + 1))
                w = out[i]
                if index[w] < 0:
                    index[w] = low[w] = counter
                    counter += 1
                    stack.append(w)
                    on_stack[w] = True
                    work.append((w, 0))
                elif on_stack[w]:
                    low[v] = min(low[v], index[w])
                continue
            if work:
                low[work[-1][0]] = min(low[work[-1][0]], low[v])
            if low[v] == index[v]:
                while True:
                    w = stack.pop()
                    on_stack[w] = False
                    comp[w] = ncomp
                    if w == v:
                        break
                ncomp += 1
    return comp


def adjacency(n: int, edges: Sequence[Edge]) -> List[List[int]]:
    succ: List[List[int]] = [[] for _ in range(n)]
    for a, b in edges:
        succ[a].append(b)
    return succ


def cyclic(n: int, edges: Sequence[Edge]) -> bool:
    """No edge is a self-loop, so a cycle is two nodes in one component."""
    comp = sccs(n, adjacency(n, edges))
    return len(set(comp)) < n


def reaches(n: int, edges: Sequence[Edge], pairs: Sequence[Edge]) -> bool:
    """Does some ``(a, b)`` of ``pairs`` have ``b`` reaching ``a`` along
    ``edges``?  Components in topological order, the set of components each
    reaches as the bits of one integer."""
    succ = adjacency(n, edges)
    comp = sccs(n, succ)
    ncomp = 1 + max(comp, default=-1)
    below: List[int] = [1 << c for c in range(ncomp)]
    members: List[List[int]] = [[] for _ in range(ncomp)]
    for v, c in enumerate(comp):
        members[c].append(v)
    for c in range(ncomp):              # successors' components come first
        for v in members[c]:
            for w in succ[v]:
                below[c] |= below[comp[w]]
    return any(below[comp[b]] >> comp[a] & 1 for a, b in pairs)


def realtime_chain(invoke: Sequence[int], complete: Sequence[int]
                   ) -> Tuple[int, List[Edge]]:
    """D4.  ``T1 -> T2`` iff ``complete(T1) < invoke(T2)`` is an interval
    order, n^2 edges as Elle's realtime graph would hold them before its
    transitive reduction.  Here: one time node a completion, in history
    order; T -> its completion's node -> the next completion's node, and
    the last completion's node before T2's invocation -> T2.  Between
    transactions the reachability is the same; the time nodes are numbered
    after the transactions.  Returns (time nodes, edges)."""
    n = len(complete)
    order = sorted(range(n), key=lambda t: complete[t])
    edges: List[Edge] = []
    for j, t in enumerate(order):
        edges.append((t, n + j))
        if j:
            edges.append((n + j - 1, n + j))
    done = [complete[t] for t in order]
    j = -1
    for t in sorted(range(n), key=lambda t: invoke[t]):
        if invoke[t] < 0:               # invocation unknown: nothing before
            continue
        while j + 1 < n and done[j + 1] < invoke[t]:
            j += 1
        if j >= 0:
            edges.append((n + j, t))
    return n, edges


def realtime_pairs(invoke: Sequence[int], complete: Sequence[int]
                   ) -> Tuple[int, List[Edge]]:
    """The same order as an edge per pair: what the tests hold
    :func:`realtime_chain` against."""
    n = len(complete)
    return 0, [(a, b) for a in range(n) for b in range(n)
               if a != b and 0 <= invoke[b] and complete[a] < invoke[b]]


def check(records: Sequence[Any], realtime: bool = True,
          rt_edges: Callable[..., Tuple[int, List[Edge]]] = realtime_chain
          ) -> Dict[str, Any]:
    # D2.  Elle keeps a crashed transaction as a node whose appends, once
    # read, are known to have happened.  Here, as in the program, only ok
    # transactions are nodes: an element whose appender is not ok orders
    # nothing.  Fewer edges, so nothing is refuted that Elle would pass.
    oks: List[int] = []
    invoke: List[int] = []
    failed: Set[Tuple[Any, Any]] = set()
    open_invokes: Dict[Any, int] = {}
    for i, r in enumerate(records):
        if r.type == INVOKE:
            open_invokes[r.process] = i
            continue
        inv = open_invokes.pop(r.process, -1)
        if r.type == OK:
            oks.append(i)
            invoke.append(inv)
        elif r.type == FAIL:
            txn = r.value or (records[inv].value if inv >= 0 else ())
            failed.update((k, v) for f, k, v in txn if f == "append")
    n = len(oks)
    types: Set[str] = set()

    # D3.  Elle also checks each transaction against itself (``internal``)
    # and each read for repeated elements; the program's host pass does
    # neither, and the generator's transactions read their own appends.
    writer: Dict[Tuple[Any, Any], int] = {}
    last_append: Dict[Tuple[int, Any], Any] = {}
    reads: List[Tuple[int, Any, List[Any]]] = []
    for t, i in enumerate(oks):
        for f, k, v in records[i].value:
            if f == "append":
                if (k, v) in writer:
                    types.add("duplicate-appends")
                writer[(k, v)] = t
                last_append[(t, k)] = v
            elif v is not None:
                reads.append((t, k, list(v)))

    longest: Dict[Any, List[Any]] = {}
    for t, k, lst in reads:
        if any((k, x) in failed for x in lst):
            types.add("G1a")
        if k not in longest or len(lst) > len(longest[k]):
            longest[k] = lst
    for t, k, lst in reads:
        if longest[k][:len(lst)] != lst:
            types.add("incompatible-order")
        if lst:
            w = writer.get((k, lst[-1]))
            if w is not None and w != t and last_append[(w, k)] != lst[-1]:
                types.add("G1b")

    # D1.  An append no read observed still has a place: the list only
    # grows, so it follows the longest read of its key and every state a
    # read observed.  Elle orders only what reads show; the program infers
    # this too (it is what makes two blind appends a visible write skew).
    unobserved: Dict[Any, List[Tuple[Any, int]]] = {}
    observed = {k: set(order) for k, order in longest.items()}
    for (k, v), t in writer.items():
        if k in observed and v not in observed[k]:
            unobserved.setdefault(k, []).append((v, t))

    kinds: Dict[Edge, Set[str]] = {}

    def edge(a: Any, b: Any, kind: str) -> None:
        if a is not None and b is not None and a != b:
            kinds.setdefault((a, b), set()).add(kind)

    for k, order in longest.items():
        ws = [writer.get((k, x)) for x in order]
        for a, b in zip(ws, ws[1:]):
            edge(a, b, WW)
        if order:
            for _, u in unobserved.get(k, ()):
                edge(ws[-1], u, WW)
    for t, k, lst in reads:
        order = longest[k]
        if lst:
            edge(writer.get((k, lst[-1])), t, WR)
        if len(lst) < len(order) and order[:len(lst)] == lst:
            edge(t, writer.get((k, order[len(lst)])), RW)
        seen = set(lst)
        for x, u in unobserved.get(k, ()):
            if x not in seen:
                edge(t, u, RW)

    def layer(*want: str) -> List[Edge]:
        return [e for e, ks in kinds.items() if ks & set(want)]

    complete = oks
    extra, rt = rt_edges(invoke, complete) if realtime else (0, [])
    nodes = n + extra
    nonrw = layer(WW, WR)
    all_rw = layer(RW)
    forced_rw = [e for e, ks in kinds.items() if ks == {RW}]
    wr_only = [e for e, ks in kinds.items() if WR in ks and WW not in ks]
    flags = {
        "cyclic": cyclic(nodes, layer(WW, WR, RW) + rt),
        "g0": cyclic(nodes, layer(WW) + rt),
        "g1c": cyclic(nodes, nonrw + rt),
        "g-single": reaches(nodes, nonrw + rt, all_rw),
    }

    def family(name: str, plain: Callable[[List[Edge]], bool]) -> None:
        if plain([]):
            types.add(name)
        elif rt and plain(rt):
            types.add(name + "-realtime")

    family("G0", lambda t: cyclic(nodes, layer(WW) + t))
    # a cycle of ww and wr edges that needs a wr edge: a -> b is one that
    # offers no ww, and b comes back to a
    family("G1c", lambda t: reaches(nodes, nonrw + t, wr_only))
    # exactly one anti-dependency: an edge that offers rw alone, closed
    # without another
    family("G-single", lambda t: reaches(nodes, nonrw + t, forced_rw))
    named = any(t.startswith(CYCLE_FAMILIES) for t in types)
    return {"valid": not types and not flags["cyclic"],
            "anomaly_types": sorted(types), "flags": flags, "count": n,
            "unnamed_cycle": flags["cyclic"] and not named}
