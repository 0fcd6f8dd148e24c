"""A plain reference for the read/write-register checker: Elle's rw-register
inference (Kingsbury & Alvaro, VLDB 2020, section 4 "read-write registers")
over the benchmark's plain records, and cyclicity by Tarjan's strongly
connected components.  Imports nothing of the program; no matrices, no
device, no search for witnesses.

``check(records, realtime=True)`` returns what ``elle_list_append.check``
returns (``valid``, ``anomaly_types``, the four ``flags``, ``count``,
``unnamed_cycle``), and ``decided`` names the types as that module's does.

Inference, per key: a *version* is a value of the key, ``None`` the initial
one.  The version graph orders two versions by each of these sources:
``initial`` (``None`` before every value an ok transaction wrote); ``wfr``
(a transaction that read v and then wrote v' first, v before v'); ``ww-txn``
(a transaction that wrote v and then v', v before v'); and, with
``realtime``, the linearizable-keys order, as the plain all-pairs rule: the
value a transaction left on the key before the value another left there,
whenever the first completed before the second was invoked.  Then: wr from
the writer of a value to each transaction that read it from outside
(before writing the key itself); ww from the writer of a version to the
writer of each version after it in the graph; rw from each outside reader
of a version to the writer of each version after it.  From the records
alone: ``G1a`` (an outside read of a value a failed transaction wrote),
``G1b`` (an outside read of a value its writer then overwrote itself),
``duplicate-writes``, and ``cyclic-versions`` (a key whose version graph
has a cycle).

Departures from Elle's published inference, each beside its code below:
the wfr source always on (D1); crashed (``info``) transactions are no nodes
(D2); no ``internal`` check and no sequential-keys order (D3); the realtime
order is a chain of time nodes (D4); the cycle anomalies named are those
that reachability decides (D5); of the linearizable-keys order's pairs,
those with no value between them (D6).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Iterable, List, Sequence, Set, Tuple

from reference.elle_list_append import reaches, realtime_chain, sccs

INVOKE, OK, FAIL = "invoke", "ok", "fail"
WW, WR, RW = 1, 2, 4

#: D5, as in ``elle_list_append``: the families whose cycles reachability
#: decides, and the anomalies of the records alone
HOST_TYPES = ("G1a", "G1b", "duplicate-writes", "cyclic-versions")
CYCLE_FAMILIES = ("G0", "G1c", "G-single")
DECIDED = frozenset(HOST_TYPES) | {f + s for f in CYCLE_FAMILIES
                                   for s in ("", "-realtime")}

Edge = Tuple[int, int]


def decided(anomaly_types: Iterable[str]) -> Set[str]:
    """A checker's anomaly types as this reference would name them (see
    ``elle_list_append.decided``)."""
    types = set(anomaly_types) & DECIDED
    return {t for t in types
            if not (t.endswith("-realtime") and t[:-len("-realtime")] in types)}


def cyclic(n: int, edges: Sequence[Edge]) -> bool:
    """A cycle: two nodes in one component, or a node its own successor."""
    succ: List[List[int]] = [[] for _ in range(n)]
    for a, b in edges:
        if a == b:
            return True
        succ[a].append(b)
    return len(set(sccs(n, succ))) < n


def check(records: Sequence[Any], realtime: bool = True) -> Dict[str, Any]:
    # D2.  As in ``elle_list_append``: only ok transactions are nodes; a
    # value only a crashed transaction wrote has no writer, orders nothing
    # by itself and gives its readers no wr edge.
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
            failed.update((k, v) for f, k, v in txn if f == "w")
    n = len(oks)
    types: Set[str] = set()

    # D3.  Elle also checks each transaction against itself (``internal``)
    # and can order a key's writes by process (``sequential-keys``); the
    # program's host pass has no internal check, and the configuration
    # asks for no sequential order.
    writer: Dict[Tuple[Any, Any], int] = {}
    overwritten: Dict[Tuple[Any, Any], int] = {}
    outside: List[Tuple[int, Any, Any]] = []       # (reader, key, value)
    before: Set[Tuple[Any, Any, Any]] = set()      # (key, v, v')
    left: Dict[Any, List[Tuple[int, Any]]] = {}    # key -> (txn, last value)
    for t, i in enumerate(oks):
        wrote: Dict[Any, Any] = {}
        read: Dict[Any, Any] = {}
        for f, k, v in records[i].value:
            if f == "r":
                if k not in wrote:
                    outside.append((t, k, v))
                read[k] = v
                continue
            if (k, v) in writer:
                types.add("duplicate-writes")
            writer[(k, v)] = t
            if k in wrote:                          # ww-txn
                overwritten[(k, wrote[k])] = t
                before.add((k, wrote[k], v))
            elif k in read and read[k] != v:
                # D1.  Elle offers "writes follow reads" behind an option
                # (``:wfr-keys?``); the program always infers it, and so
                # does this reference.
                before.add((k, read[k], v))
            wrote[k] = v
        for k, v in wrote.items():
            left.setdefault(k, []).append((t, v))
    for (k, v) in writer:                           # initial
        if v is not None:
            before.add((k, None, v))
    if realtime:
        # D6.  Linearizable keys: the values two writers of a key left are
        # ordered whenever the first completed before the second was
        # invoked, every pair tested.  The pairs kept are those the order
        # has no third value between: b after a where no c after a had
        # completed when b was invoked.  A pair left out is a path of pairs
        # kept, through values ok transactions left, so every reachability
        # below is the all-pairs order's, without its n^2 edges (1.4 million
        # on the cell's 10,000 transactions).
        for k, ws in left.items():
            for a, va in ws:
                after = [(b, vb) for b, vb in ws if oks[a] < invoke[b]]
                if not after:
                    continue
                first_done = min(oks[b] for b, _ in after)
                for b, vb in after:
                    if invoke[b] <= first_done and va != vb:
                        before.add((k, va, vb))

    for t, k, v in outside:
        if (k, v) in failed:
            types.add("G1a")
        if overwritten.get((k, v), t) != t:
            types.add("G1b")

    by_key: Dict[Any, Set[Tuple[Any, Any]]] = {}
    for k, v, w in before:
        by_key.setdefault(k, set()).add((v, w))
    for k, pairs in by_key.items():
        names: Dict[Any, int] = {}
        for v, w in pairs:
            names.setdefault(v, len(names))
            names.setdefault(w, len(names))
        if cyclic(len(names), [(names[v], names[w]) for v, w in pairs]):
            types.add("cyclic-versions")

    readers: Dict[Tuple[Any, Any], List[int]] = {}
    for t, k, v in outside:
        readers.setdefault((k, v), []).append(t)
    kinds: Dict[Edge, int] = {}

    def edge(a: Any, b: Any, kind: int) -> None:
        if a is not None and b is not None and a != b:
            kinds[(a, b)] = kinds.get((a, b), 0) | kind

    for t, k, v in outside:
        if v is not None:
            edge(writer.get((k, v)), t, WR)
    for k, v, w in before:
        later = writer.get((k, w))
        if later is None:
            continue
        edge(writer.get((k, v)), later, WW)
        for r in readers.get((k, v), ()):
            edge(r, later, RW)

    # D4.  The realtime order as ``elle_list_append.realtime_chain`` holds
    # it: one time node a completion.
    complete = oks
    extra, rt = realtime_chain(invoke, complete) if realtime else (0, [])
    nodes = n + extra

    def layer(mask: int) -> List[Edge]:
        return [e for e, ks in kinds.items() if ks & mask]

    nonrw = layer(WW | WR)
    forced_rw = [e for e, ks in kinds.items() if ks == RW]
    wr_only = [e for e, ks in kinds.items() if ks & WR and not ks & WW]
    flags = {
        "cyclic": cyclic(nodes, layer(WW | WR | RW) + rt),
        "g0": cyclic(nodes, layer(WW) + rt),
        "g1c": cyclic(nodes, nonrw + rt),
        "g-single": reaches(nodes, nonrw + rt, layer(RW)),
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
