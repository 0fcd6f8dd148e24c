"""The benchmark's own history generators: one general generator per kind
of traffic, driven by a traffic file's parameters.

Copies of the program's seeded generators (``jepsen_tpu/synth.py``:
``cas_register_history``, ``corrupt_reads``, ``doomed_cas_padding``;
``chip_smoke.py``: ``keyed_lanes`` and ``phase_keyed``'s keyed history), so
that a later PR may change ``synth.py`` and cannot change the traffic.  They
import nothing of the program and produce plain :class:`Rec` tuples; the
harness turns those into the program's ``Op`` objects at the entry, and the
plain reference reads them as they are.

What ``--seed`` does.  The *structure* of a history (who invokes what, when,
which process crashes) comes from the ``history_seed`` fixed in the traffic
file, so every run of a cell gives the checker the same search to do: the
same window, the same capacity ladder, the same number of configurations.
``--seed`` draws a relabeling from that structure's symmetry group: a
permutation of the register's value alphabet, a permutation of the process
ids and, for keyed traffic, the order of the keys.  The same seed gives the
same history, another seed gives another history, and no seed changes the
work (see PERF.md, "the seed").
"""

from __future__ import annotations

import random
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

INVOKE, OK, FAIL, INFO = "invoke", "ok", "fail", "info"


class Rec(NamedTuple):
    """One history entry, in the program's field names."""

    process: Any
    type: str
    f: str
    value: Any = None
    time: Optional[int] = None
    error: Any = None


def cas_register_history(n_ops: int, concurrency: int = 5, values: int = 5,
                         crash_p: float = 0.003, seed: int = 0,
                         read_p: float = 0.5,
                         write_p: float = 0.25) -> List[Rec]:
    """``n_ops`` reads/writes/cas against one register, simulated: invokes,
    effects and completions interleave freely, so the history is
    linearizable by construction.  Crashed ops (probability ``crash_p``)
    become ``info``; half of the crashed mutations still take effect later.
    Draw for draw the program's ``synth.cas_register_history``."""
    rng = random.Random(seed)
    state: Optional[int] = None
    history: List[Rec] = []
    free = list(range(concurrency))
    pending: Dict[int, Dict[str, Any]] = {}
    ghost_effects: List[Dict[str, Any]] = []
    t = 0
    invoked = 0

    def effect(p: int) -> None:
        nonlocal state
        d = pending[p]
        op = d["op"]
        if op.f == "read":
            d["result_value"] = state
            d["result_type"] = OK
        elif op.f == "write":
            state = op.value
            d["result_value"] = op.value
            d["result_type"] = OK
        else:
            old, new = op.value
            if state == old:
                state = new
                d["result_type"] = OK
            else:
                d["result_type"] = FAIL
            d["result_value"] = op.value
        d["effected"] = True

    while invoked < n_ops or pending:
        t += rng.randint(1, 1000)
        if ghost_effects and rng.random() < 0.3:
            ge = ghost_effects.pop(rng.randrange(len(ghost_effects)))
            if ge["op"].f == "write":
                state = ge["op"].value
            elif ge["op"].f == "cas":
                old, new = ge["op"].value
                if state == old:
                    state = new
        roll = rng.random()
        if free and invoked < n_ops and (roll < 0.45 or not pending):
            p = free.pop(rng.randrange(len(free)))
            r = rng.random()
            if r < read_p:
                op = Rec(p, INVOKE, "read", None, t)
            elif r < read_p + write_p:
                op = Rec(p, INVOKE, "write", rng.randrange(values), t)
            else:
                op = Rec(p, INVOKE, "cas",
                         [rng.randrange(values), rng.randrange(values)], t)
            history.append(op)
            pending[p] = {"op": op, "effected": False,
                          "result_type": None, "result_value": None}
            invoked += 1
        elif pending:
            p = rng.choice(list(pending))
            d = pending[p]
            if rng.random() < crash_p:
                history.append(Rec(p, INFO, d["op"].f, None, t, "crashed"))
                if not d["effected"] and d["op"].f != "read" \
                        and rng.random() < 0.5:
                    ghost_effects.append(d)
                del pending[p]
                free.append(p)
            elif not d["effected"]:
                effect(p)
            else:
                history.append(Rec(p, d["result_type"], d["op"].f,
                                   d["result_value"], t))
                del pending[p]
                free.append(p)
    return history


def doomed_cas_padding(n: int, start_process: int = 9000,
                       base_expect: int = 7777) -> List[Rec]:
    """``n`` crashed CAS ops whose expected value no write ever produces:
    they hold a pending-window slot each for ever and can never be
    linearized.  The program's ``synth.doomed_cas_padding``."""
    return ([Rec(start_process + i, INVOKE, "cas", [base_expect + i, 1])
             for i in range(n)]
            + [Rec(start_process + i, INFO, "cas", None) for i in range(n)])


def corrupt_reads(history: Sequence[Rec], n: int = 1, seed: int = 0,
                  values: int = 5,
                  within: Optional[float] = None) -> List[Rec]:
    """Flip the value of ``n`` ok-reads to one outside the value domain.
    The program's ``synth.corrupt_reads``."""
    rng = random.Random(seed)
    ops = list(history)
    cut = len(ops) if within is None else max(1, int(len(ops) * within))
    read_oks = [i for i, o in enumerate(ops[:cut])
                if o.type == OK and o.f == "read"]
    if not read_oks:
        raise ValueError("no ok reads to corrupt")
    for i in rng.sample(read_oks, min(n, len(read_oks))):
        ops[i] = ops[i]._replace(value=values + 1000 + rng.randrange(100))
    return ops


# ---------------------------------------------------------------------------
# The seed's relabeling
# ---------------------------------------------------------------------------

def _relabel_value(v: Any, perm: Sequence[int]) -> Any:
    """Values of the alphabet go through ``perm``; anything else (``None``,
    a doomed CAS's expectation, a corrupted read) stays what it is."""
    if isinstance(v, list):
        return [_relabel_value(x, perm) for x in v]
    if isinstance(v, int) and 0 <= v < len(perm):
        return perm[v]
    return v


def relabel(history: Sequence[Rec], rng: random.Random,
            values: int) -> List[Rec]:
    """One draw from the history's symmetries: permute the value alphabet
    and rename the processes.  Linearizability, the refuting op's position
    and the size of the search are unchanged by it."""
    perm = list(range(values))
    rng.shuffle(perm)
    procs = sorted({o.process for o in history})
    names = list(procs)
    rng.shuffle(names)
    rename = dict(zip(procs, names))
    return [o._replace(process=rename[o.process],
                       value=_relabel_value(o.value, perm))
            for o in history]


# ---------------------------------------------------------------------------
# Generators, by the name a traffic file gives
# ---------------------------------------------------------------------------

def single_register(config: Dict[str, Any], params: Dict[str, Any],
                    seed: int) -> Dict[str, Any]:
    """One register's history: optional doomed CAS padding in front, then
    the simulated workload, optionally with corrupted reads."""
    values = int(config["values"])
    work = cas_register_history(
        int(config["ops"]), concurrency=int(config["concurrency"]),
        values=values, crash_p=float(params["crash_p"]),
        seed=int(params["history_seed"]),
        read_p=float(config["read_p"]), write_p=float(config["write_p"]))
    if params.get("corrupt_reads"):
        work = corrupt_reads(work, n=int(params["corrupt_reads"]),
                             seed=int(params["history_seed"]), values=values,
                             within=params.get("corrupt_within"))
    recs = doomed_cas_padding(int(params.get("doomed_cas", 0))) + work
    return {"keyed": False,
            "records": relabel(recs, random.Random(seed), values)}


def keyed_registers(config: Dict[str, Any], params: Dict[str, Any],
                    seed: int) -> Dict[str, Any]:
    """``keys`` independent registers in one history, values wrapped as
    ``(key, value)``: ``concurrent_keys`` keys run side by side, each on its
    own group of processes (jepsen.independent/concurrent-generator), and
    their entries merge by time.  Every ``refute_every``-th lane has one
    corrupted read.  The seed orders the lanes and relabels each."""
    values = int(config["values"])
    n_keys, n_ops = int(config["keys"]), int(config["ops_per_key"])
    base = int(params["history_seed"])
    every = int(params.get("refute_every", 0))
    lanes = []
    for i in range(n_keys):
        h = cas_register_history(
            n_ops, concurrency=int(config["processes_per_key"]),
            values=values, crash_p=float(params["crash_p"]), seed=base + i,
            read_p=float(config["read_p"]), write_p=float(config["write_p"]))
        if every and i % every == 0:
            h = corrupt_reads(h, n=1, seed=base + i, values=values)
        lanes.append(h)
    rng = random.Random(seed)
    rng.shuffle(lanes)
    stride = int(params.get("process_stride", 10))
    group = max(1, int(params.get("concurrent_keys", 1)))
    records: List[Rec] = []
    for g0 in range(0, n_keys, group):
        merged = []
        for k in range(g0, min(g0 + group, n_keys)):
            for j, o in enumerate(relabel(lanes[k], rng, values)):
                merged.append((o.time, k, j, o._replace(
                    process=o.process + stride * k, value=(k, o.value))))
        merged.sort(key=lambda e: e[:3])
        records.extend(e[3] for e in merged)
    return {"keyed": True, "records": records}


GENERATORS = {"single_register": single_register,
              "keyed_registers": keyed_registers}


def split_keys(records: Sequence[Rec]) -> Dict[Any, List[Rec]]:
    """Per-key sub-histories of a keyed history, values unwrapped, keys in
    first-appearance order (what jepsen.independent's checker checks)."""
    out: Dict[Any, List[Rec]] = {}
    for o in records:
        k, v = o.value
        out.setdefault(k, []).append(o._replace(value=v))
    return out
