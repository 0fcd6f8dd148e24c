"""One run's list-append history: the transactions of the reference's
append workload (``jepsen/src/jepsen/tests/cycle/append.clj:11-46`` over
``elle.list-append/gen``), simulated against an atomic per-key list store.

The transactions are Elle's: ``min_txn_length`` to ``max_txn_length``
micro-ops, each a read ``["r", k, None]`` or an append ``["append", k, v]``
with probability ``1 - read_p``, on one of ``key_count`` *active* keys.  A
key takes the values 1, 2, ... and is retired once it has been given
``max_writes_per_key`` of them: the next fresh key takes its place in the
pool, so a read's list never grows past that many elements.  The key of a
micro-op is the pool's slot ``i`` with weight ``key_dist_base ** i``
(``exponential``: the newest slots take most of the traffic) or any slot
alike (``uniform``).

The store is ``synth.list_append_history``'s: invokes and completions
interleave freely over ``concurrency`` threads, and every transaction takes
effect atomically at its completion, so the history is strict-serializable
by construction.  ``fail_p`` of the transactions abort (``fail``: nothing
applied), ``info_p`` crash (``info``: ``info_applied_p`` of those still
applied, at the crash; the thread goes on under a new process id, as a
Jepsen thread does).  Plain :class:`gen.histories.Rec` tuples; imports
nothing of the program.

``history_seed`` fixes the structure; ``--seed`` draws one relabeling from
its symmetries: the processes renamed, the keys renamed, and each key's
values permuted.  Who reads what of whom, and so the dependency graph, its
size and the work, are unchanged by it.

The corruptors at the end serve the tests and the controls: each breaks one
stated guarantee in one place.
"""

from __future__ import annotations

import math
import random
from typing import Any, Dict, List, Optional, Sequence, Tuple

from gen.histories import FAIL, INFO, INVOKE, OK, Rec


class TxnSource:
    """Elle's ``wr-txns`` state: the pool of active keys and the next value
    of each."""

    def __init__(self, rng: random.Random, key_count: int,
                 max_writes_per_key: int, min_txn_length: int,
                 max_txn_length: int, read_p: float, key_dist: str,
                 key_dist_base: float = 2.0) -> None:
        if key_dist not in ("exponential", "uniform"):
            raise ValueError(f"unknown key_dist {key_dist!r}")
        self.rng = rng
        self.active = list(range(key_count))
        self.next_key = key_count
        self.next_value: Dict[int, int] = {}
        self.max_writes = max_writes_per_key
        self.lengths = (min_txn_length, max_txn_length)
        self.read_p = read_p
        self.exponential = key_dist == "exponential"
        self.base = key_dist_base
        # rand * scale + base lies in [base, base ** (key_count + 1)): its
        # logarithm less one, floored, is a slot, slot i with weight base**i
        self.scale = (key_dist_base ** key_count - 1) * key_dist_base \
            / (key_dist_base - 1)

    def slot(self) -> int:
        if not self.exponential:
            return self.rng.randrange(len(self.active))
        x = self.rng.random() * self.scale + self.base
        return min(len(self.active) - 1,
                   int(math.log(x) / math.log(self.base)) - 1)

    def txn(self) -> List[List[Any]]:
        out: List[List[Any]] = []
        for _ in range(self.rng.randint(*self.lengths)):
            i = self.slot()
            k = self.active[i]
            if self.rng.random() < self.read_p:
                out.append(["r", k, None])
                continue
            v = self.next_value.get(k, 1)
            out.append(["append", k, v])
            self.next_value[k] = v + 1
            if v >= self.max_writes:            # retired: a fresh key
                self.active[i] = self.next_key
                self.next_key += 1
        return out


def list_append_history(n_txns: int, concurrency: int = 10,
                        key_count: int = 10, max_writes_per_key: int = 256,
                        min_txn_length: int = 1, max_txn_length: int = 4,
                        read_p: float = 0.5, key_dist: str = "exponential",
                        key_dist_base: float = 2.0, fail_p: float = 0.05,
                        info_p: float = 0.0, info_applied_p: float = 0.5,
                        seed: int = 0) -> List[Rec]:
    """``n_txns`` transactions, invoked and completed in one interleaving."""
    rng = random.Random(seed)
    source = TxnSource(rng, key_count, max_writes_per_key, min_txn_length,
                       max_txn_length, read_p, key_dist, key_dist_base)
    state: Dict[int, List[int]] = {}
    history: List[Rec] = []
    free = list(range(concurrency))
    pending: Dict[int, Tuple[List[List[Any]], float]] = {}
    t = 0
    invoked = 0

    def apply(txn: List[List[Any]]) -> List[List[Any]]:
        filled = []
        for f, k, v in txn:
            if f == "append":
                state[k] = state.get(k, []) + [v]
                filled.append(["append", k, v])
            else:
                filled.append(["r", k, list(state.get(k, []))])
        return filled

    while invoked < n_txns or pending:
        t += rng.randint(1, 1000)
        if free and invoked < n_txns and (rng.random() < 0.55
                                          or not pending):
            p = free.pop(rng.randrange(len(free)))
            txn = source.txn()
            history.append(Rec(p, INVOKE, "txn", txn, t))
            pending[p] = (txn, rng.random())
            invoked += 1
        elif pending:
            p = rng.choice(list(pending))
            txn, fate = pending.pop(p)
            if fate < fail_p:
                history.append(Rec(p, FAIL, "txn", txn, t))
            elif fate < fail_p + info_p:
                if rng.random() < info_applied_p:
                    apply(txn)
                history.append(Rec(p, INFO, "txn", txn, t, "crashed"))
                p += concurrency
            else:
                history.append(Rec(p, OK, "txn", apply(txn), t))
            free.append(p)
    return history


def relabel(history: Sequence[Rec], rng: random.Random) -> List[Rec]:
    """One draw from the history's symmetries: processes, keys and each
    key's values renamed."""
    procs = sorted({o.process for o in history})
    names = list(procs)
    rng.shuffle(names)
    rename = dict(zip(procs, names))
    top: Dict[int, int] = {}
    for o in history:
        if o.type == INVOKE:
            for f, k, v in o.value:
                if f == "append":
                    top[k] = max(top.get(k, 0), v)
                else:
                    top.setdefault(k, 0)
    keys = sorted(top)
    knames = list(keys)
    rng.shuffle(knames)
    kperm = dict(zip(keys, knames))
    vperm: Dict[int, List[int]] = {}
    for k in keys:                      # value v of key k -> vperm[k][v]
        vs = list(range(1, top[k] + 1))
        rng.shuffle(vs)
        vperm[k] = [0] + vs

    def mop(m: List[Any]) -> List[Any]:
        f, k, v = m
        if f == "append":
            return [f, kperm[k], vperm[k][v]]
        return [f, kperm[k], None if v is None else [vperm[k][x] for x in v]]

    return [o._replace(process=rename[o.process],
                       value=[mop(m) for m in o.value]) for o in history]


def list_append(config: Dict[str, Any], params: Dict[str, Any],
                seed: int) -> Dict[str, Any]:
    """The configuration's one history, relabeled by the seed."""
    recs = list_append_history(
        int(config["txns"]), concurrency=int(config["concurrency"]),
        key_count=int(config["key_count"]),
        max_writes_per_key=int(config["max_writes_per_key"]),
        min_txn_length=int(config["min_txn_length"]),
        max_txn_length=int(config["max_txn_length"]),
        read_p=float(config["read_p"]), key_dist=config["key_dist"],
        key_dist_base=float(config.get("key_dist_base", 2)),
        fail_p=float(params["fail_p"]), info_p=float(params["info_p"]),
        info_applied_p=float(params["info_applied_p"]),
        seed=int(params["history_seed"]))
    return {"keyed": False, "records": relabel(recs, random.Random(seed))}


GENERATORS = {"list_append": list_append}


# -- corruptors: one guarantee broken in one place ---------------------------

def _ok_reads(history: Sequence[Rec]) -> List[Tuple[int, int]]:
    """(entry, micro-op) of every ok read that observed something."""
    return [(i, j) for i, o in enumerate(history) if o.type == OK
            for j, (f, _, v) in enumerate(o.value) if f == "r" and v]


def _with_read(history: Sequence[Rec], i: int, j: int,
               lst: List[int]) -> List[Rec]:
    out = list(history)
    value = [list(m) for m in out[i].value]
    value[j][2] = lst
    out[i] = out[i]._replace(value=value)
    return out


def _invoke_of(history: Sequence[Rec], i: int) -> int:
    p = history[i].process
    return max(j for j in range(i) if history[j].process == p
               and history[j].type == INVOKE)


def _appender_done(history: Sequence[Rec], k: int, v: int) -> Optional[int]:
    """Entry of the ok completion that appended ``v`` to ``k``."""
    for i, o in enumerate(history):
        if o.type == OK and ["append", k, v] in o.value:
            return i
    return None


def stale_read(history: Sequence[Rec], rng: random.Random) -> List[Rec]:
    """One ok read loses its last element, whose appender had completed
    before the reader was invoked: a read of a state that was already
    overwritten when the transaction began."""
    reads = _ok_reads(history)
    rng.shuffle(reads)
    for i, j in reads:
        _, k, lst = history[i].value[j]
        done = _appender_done(history, k, lst[-1])
        if done is not None and done < _invoke_of(history, i):
            return _with_read(history, i, j, lst[:-1])
    raise ValueError("no read to make stale")


def swapped_read(history: Sequence[Rec], rng: random.Random) -> List[Rec]:
    """One ok read's last two elements change places."""
    reads = [(i, j) for i, j in _ok_reads(history)
             if len(history[i].value[j][2]) >= 2]
    i, j = rng.choice(reads)
    lst = list(history[i].value[j][2])
    lst[-1], lst[-2] = lst[-2], lst[-1]
    return _with_read(history, i, j, lst)


def aborted_read(history: Sequence[Rec], rng: random.Random) -> List[Rec]:
    """One ok read observes, at its end, a value whose append failed."""
    failed = {(k, v) for o in history if o.type == FAIL
              for f, k, v in o.value if f == "append"}
    reads = _ok_reads(history)
    rng.shuffle(reads)
    for i, j in reads:
        k = history[i].value[j][1]
        mine = sorted(v for kk, v in failed if kk == k)
        if mine:
            return _with_read(history, i, j,
                              list(history[i].value[j][2]) + [mine[0]])
    raise ValueError("no failed append to read")


def late_reader(history: Sequence[Rec], rng: random.Random) -> List[Rec]:
    """One read-only ok transaction is moved, both entries, to the end of
    the history under a process of its own: what it observed is still one
    state of the store, so the history stays serializable, but every
    append that state lacks had completed before the reader began."""
    cands = [i for i, o in enumerate(history) if o.type == OK
             and o.value[0][2] is not None
             and all(f == "r" for f, _, _ in o.value)]
    rng.shuffle(cands)
    for i in cands:
        k, v = history[i].value[0][1], history[i].value[0][2]
        later = [o for o in history[i + 1:] if o.type == OK and any(
            f == "append" and kk == k and x not in v
            for f, kk, x in o.value)]
        if not later:
            continue
        inv = _invoke_of(history, i)
        p = 1 + max(o.process for o in history)
        t = history[-1].time
        rest = [o for n, o in enumerate(history) if n not in (inv, i)]
        return rest + [history[inv]._replace(process=p, time=t + 1),
                       history[i]._replace(process=p, time=t + 2)]
    raise ValueError("no read-only transaction to move")


CORRUPTORS = {"stale_read": stale_read, "swapped_read": swapped_read,
              "aborted_read": aborted_read, "late_reader": late_reader}
