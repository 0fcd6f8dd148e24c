"""Dependency graphs, SCCs, and cycle extraction.

The backbone of the anomaly checkers: nodes are transaction ids, labelled
edges carry dependency types (ww/wr/rw/realtime/process).  Tarjan SCC
(iterative — histories are long) plus shortest-cycle recovery inside an SCC.
"""

from __future__ import annotations

import time
from collections import defaultdict, deque
from typing import Any, Dict, FrozenSet, Iterable, List, Optional, Set, Tuple


class SearchBudget:
    """Work/time guard for cycle recovery.

    Witness recovery is best-effort by nature (the verdict-deciding pass is
    the closure / SCC scan); on a huge SCC the peel-and-research loop in
    :func:`peeled_cycles` is O(cycles * E) and the per-start BFS of
    :func:`find_cycle` is O(|C| * E) — enough to wedge the budgeted checker
    path (checker/core.py check_safe) on a pathological history.  The budget
    caps both a step counter (coarse-grained: nodes touched per peel / BFS
    expansions) and, optionally, a wall-clock deadline; exhaustion flips
    ``truncated`` and the searches stop yielding.  Callers surface the flag
    as ``cycle-search-truncated`` so a truncated pass can never silently
    certify a history (finish_result degrades a clean verdict to unknown).
    """

    #: default step ceiling — generous (a 10k-txn history's full suite
    #: spends well under 10% of this) but finite, so the CPU fallback path
    #: is bounded even when no explicit budget was configured.
    DEFAULT_MAX_STEPS = 20_000_000
    #: SCCs beyond this many nodes are reported truncated, not searched.
    DEFAULT_MAX_SCC_NODES = 200_000
    #: cap on shortest-cycle BFS starts inside one component (the search
    #: stays correct — any cycle is a witness — just not globally shortest).
    DEFAULT_MAX_CYCLE_STARTS = 2_000

    def __init__(self, deadline_s: Optional[float] = None,
                 max_steps: int = DEFAULT_MAX_STEPS,
                 max_scc_nodes: int = DEFAULT_MAX_SCC_NODES,
                 max_cycle_starts: int = DEFAULT_MAX_CYCLE_STARTS):
        self.deadline = (time.monotonic() + deadline_s
                         if deadline_s is not None else None)
        self.steps = max_steps
        self.max_scc_nodes = max_scc_nodes
        self.max_cycle_starts = max_cycle_starts
        self.truncated = False

    def admit_scc(self, n_nodes: int) -> bool:
        if n_nodes > self.max_scc_nodes:
            self.truncated = True
            return False
        return self.spend(0)

    def spend(self, n: int = 1) -> bool:
        """Charge ``n`` work units; False (and truncated) once exhausted."""
        if self.truncated:
            return False
        self.steps -= n
        if self.steps < 0 or (self.deadline is not None
                              and time.monotonic() > self.deadline):
            self.truncated = True
            return False
        return True


class Graph:
    def __init__(self):
        self.out: Dict[Any, Dict[Any, Set[str]]] = defaultdict(dict)
        self.nodes: Set[Any] = set()

    def add_node(self, n) -> None:
        self.nodes.add(n)

    def add_edge(self, a, b, kind: str) -> None:
        if a == b:
            return
        self.nodes.add(a)
        self.nodes.add(b)
        self.out[a].setdefault(b, set()).add(kind)

    def succs(self, n) -> Iterable[Any]:
        return self.out.get(n, {})

    def edge_kinds(self, a, b) -> Set[str]:
        return self.out.get(a, {}).get(b, set())

    def subgraph(self, nodes: Iterable[Any]) -> "Graph":
        """Node-induced subgraph (edge kinds dropped — cycle *search* never
        reads kinds; report kinds from the full graph)."""
        ns = set(nodes)
        g = Graph()
        g.nodes = ns
        for a in ns:
            for b in self.succs(a):
                if b in ns:
                    g.add_edge(a, b, "")
        return g

    def filter_kinds(self, kinds: Iterable[str]) -> "Graph":
        ks = set(kinds)
        g = Graph()
        g.nodes = set(self.nodes)
        for a, bs in self.out.items():
            for b, ek in bs.items():
                inter = ek & ks
                if inter:
                    for k in inter:
                        g.add_edge(a, b, k)
        return g

    def __len__(self):
        return len(self.nodes)


def peeled_cycles(g: Graph, budget: Optional[SearchBudget] = None):
    """Yield node-disjoint cycles across the whole graph.

    ``find_cycle`` recovers one (shortest) cycle per SCC, but one SCC can
    merge several distinct anomalies (e.g. a ww 2-cycle bridged to a wr
    cycle).  After yielding a cycle, its nodes are peeled off and the
    remainder re-searched, so every node-disjoint cycle in a component is
    reported (the coverage elle's checkers get from per-SCC re-search).

    ``budget`` (:class:`SearchBudget`) bounds the peel loop: each iteration
    re-runs Tarjan over the remainder, so an adversarial SCC could cost
    O(cycles * E) — past the budget the generator just stops (the caller
    reads ``budget.truncated``)."""
    for comp in sccs(g):
        if budget is not None and not budget.admit_scc(len(comp)):
            continue
        remaining = set(comp)
        while len(remaining) >= 2:
            if budget is not None and not budget.spend(len(remaining)):
                return
            sub = g.subgraph(remaining)
            cyc = None
            for c in sccs(sub):
                if len(c) >= 2:
                    cyc = find_cycle(sub, c, budget)
                    if cyc:
                        break
            if not cyc:
                break
            remaining -= set(cyc)
            yield cyc


def sccs(g: Graph) -> List[List[Any]]:
    """Iterative Tarjan; returns nontrivial SCCs (size >= 2)."""
    index: Dict[Any, int] = {}
    low: Dict[Any, int] = {}
    on_stack: Set[Any] = set()
    stack: List[Any] = []
    out: List[List[Any]] = []
    counter = [0]

    for root in g.nodes:
        if root in index:
            continue
        work = [(root, iter(g.succs(root)))]
        index[root] = low[root] = counter[0]
        counter[0] += 1
        stack.append(root)
        on_stack.add(root)
        while work:
            node, it = work[-1]
            advanced = False
            for w in it:
                if w not in index:
                    index[w] = low[w] = counter[0]
                    counter[0] += 1
                    stack.append(w)
                    on_stack.add(w)
                    work.append((w, iter(g.succs(w))))
                    advanced = True
                    break
                elif w in on_stack:
                    low[node] = min(low[node], index[w])
            if advanced:
                continue
            work.pop()
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[node])
            if low[node] == index[node]:
                comp = []
                while True:
                    w = stack.pop()
                    on_stack.discard(w)
                    comp.append(w)
                    if w == node:
                        break
                if len(comp) > 1:
                    out.append(comp)
    return out


def find_cycle(g: Graph, component: List[Any],
               budget: Optional[SearchBudget] = None) -> Optional[List[Any]]:
    """A shortest cycle within an SCC: BFS from each node back to itself
    (bounded — component members only).  With a ``budget``, the number of
    BFS starts is capped (any recovered cycle is a valid witness; only
    global shortestness is sacrificed) and each start charges the
    component size."""
    comp = set(component)
    best: Optional[List[Any]] = None
    starts = component if budget is None \
        else component[:budget.max_cycle_starts]
    for start in starts:
        if budget is not None and not budget.spend(len(comp)):
            break
        # BFS over comp
        prev: Dict[Any, Any] = {start: None}
        q = deque([start])
        found = None
        while q and found is None:
            n = q.popleft()
            for m in g.succs(n):
                if m == start:
                    found = n
                    break
                if m in comp and m not in prev:
                    prev[m] = n
                    q.append(m)
        if found is not None:
            path = [found]
            while prev[path[-1]] is not None:
                path.append(prev[path[-1]])
            path.reverse()
            path.append(start)  # close: start -> ... -> found -> start
            cyc = [start] + path if path[0] != start else path
            # normalize: cycle as [n0, n1, ..., n0]
            if best is None or len(cyc) < len(best):
                best = cyc
        if best is not None and len(best) == 2:
            break
    return best


def cycle_edge_kinds(g: Graph, cycle: List[Any]) -> List[Set[str]]:
    return [g.edge_kinds(a, b) for a, b in zip(cycle, cycle[1:])]


def edge_list(g: Graph, cap: int = 100_000) -> List[Tuple[Any, Any, List[str]]]:
    """The graph as a flat, JSON-friendly edge list ``(src, dst, kinds)``
    for artifact export.  Capped: a dense realtime layer is O(N^2) edges
    and the artifact is a debugging aid, not the verdict."""
    out: List[Tuple[Any, Any, List[str]]] = []
    for a, bs in g.out.items():
        for b, ks in bs.items():
            out.append((a, b, sorted(ks)))
            if len(out) >= cap:
                return out
    return out


def gsingle_cycles(g: Graph, cap: int = 64,
                   budget: Optional[SearchBudget] = None):
    """Cycles with exactly one anti-dependency (rw) edge: for each rw edge
    a->b, a shortest return path b ->* a through edges that each offer a
    non-rw kind.  This is the targeted G-single search (elle runs one per
    anomaly type) — the generic shortest-cycle pass can surface a different,
    SI-legal cycle from the same SCC and miss these."""
    out = []
    for a in list(g.out):
        for b, ks in g.out[a].items():
            if "rw" not in ks:
                continue
            if budget is not None and not budget.spend(len(g)):
                return out
            # a return path that needs no realtime edge first: the shortest
            # one often takes a realtime shortcut, and the cycle would then
            # be filed as G-single-realtime (refuting the strict tier only)
            # where a plain G-single exists
            path = _bfs_path(g, b, a,
                             lambda kinds: bool(kinds - {"rw", "realtime"}))
            if path is None:
                if budget is not None and not budget.spend(len(g)):
                    return out
                path = _bfs_path(g, b, a, lambda kinds: bool(kinds - {"rw"}))
            if path is not None:
                out.append([a] + path)
                if len(out) >= cap:
                    return out
    return out


def nonadjacent_rw_cycles(g: Graph, cap: int = 64,
                          budget: int = 20000,
                          search_budget: Optional[SearchBudget] = None):
    """Cycles with >= 2 rw edges and no two adjacent around the cycle —
    the shape snapshot isolation cannot admit (every cycle in an SI
    execution carries two *consecutive* anti-dependency edges; Fekete).

    For each rw edge a->b, DFS over (node, last-edge-was-rw,
    used-a-second-rw) from (b, True, False) to an arrival at ``a`` with a
    non-rw last edge and a second (necessarily nonadjacent) rw on the
    path.  The search tracks per-path visited NODES, so every emitted
    witness is a simple cycle — a state-keyed BFS could revisit a node
    under a different flag state and file a closed *walk* as the anomaly
    (the verdict stayed sound, but the witness edges in the artifact could
    be wrong).  ``budget`` caps expansions per rw edge (simple-path search
    is worst-case exponential); on exhaustion the edge just yields no
    witness — other searches still guard the verdict."""
    out = []
    for a in list(g.out):
        for b, ks in g.out[a].items():
            if "rw" not in ks:
                continue
            if search_budget is not None and not search_budget.spend(0):
                return out
            path = _simple_nonadjacent_path(g, a, b, budget,
                                            search_budget)
            if path is None:
                continue
            out.append([a] + path)
            if len(out) >= cap:
                return out
    return out


def _simple_nonadjacent_path(
        g: Graph, a, b, budget: int,
        search_budget: Optional[SearchBudget] = None) -> Optional[List[Any]]:
    """Simple path [b, ..., a] whose first hop is non-rw-preceded (the
    caller's a->b edge was rw), containing >= 1 further rw edge, no two
    rw edges adjacent, and a non-rw arrival at ``a``."""
    stack = [(b, True, False, (b,))]
    seen_budget = budget
    while stack:
        n, last_rw, extra, path = stack.pop()
        seen_budget -= 1
        if seen_budget <= 0:
            return None
        if search_budget is not None and not search_budget.spend():
            return None
        on_path = set(path)
        for m, mks in g.out.get(n, {}).items():
            steps = []
            if mks - {"rw"}:
                steps.append((m, False, extra))
            if "rw" in mks and not last_rw:
                steps.append((m, True, True))
            for mm, lr, ex in steps:
                if mm == a:
                    if not lr and ex:
                        return list(path) + [a]
                    continue
                if mm in on_path:
                    continue
                stack.append((mm, lr, ex, path + (mm,)))
    return None


def _bfs_path(g: Graph, src, dst, edge_ok) -> Optional[List[Any]]:
    """Shortest path src ->* dst using edges where ``edge_ok(kinds)``;
    returns [src, ..., dst] (src == dst gives a self-returning path only via
    an actual cycle, never the empty path)."""
    prev: Dict[Any, Any] = {src: None}
    q = deque([src])
    while q:
        n = q.popleft()
        for m, ks in g.out.get(n, {}).items():
            if not edge_ok(ks):
                continue
            if m == dst:
                path = [m, n]
                while prev[path[-1]] is not None:
                    path.append(prev[path[-1]])
                path.reverse()
                return path
            if m not in prev:
                prev[m] = n
                q.append(m)
    return None
