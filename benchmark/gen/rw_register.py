"""One run's read/write-register history: the transactions of the
reference's ``wr`` workload (``jepsen/src/jepsen/tests/cycle/wr.clj:9-25``
over ``elle.rw-register/gen``), simulated against an atomic per-key
register store.

It is ``gen.list_append``'s run with the workload changed and nothing else:
the same key pool (``key_count`` active keys, a key retired after
``max_writes_per_key`` writes, exponential or uniform choice of slot), the
same transactions, interleaving, aborts and crashes, drawn from the same
seeds.  A write ``["w", k, v]`` stands where that run appends ``v`` to
``k``, and a read returns the register's value, which is the last element
of the list that run's read returned (``None`` for an empty list): the
register holds the last value written, as the list ends in the last value
appended.  Every transaction still takes effect atomically at its
completion, so the history is strict-serializable by construction, each
key linearizable.  ``--seed`` relabels as there (processes, keys, each
key's values), so the dependency graph and the work are every seed's.

The corruptors at the end serve the probes of ``correct`` and the tests:
each breaks one stated guarantee in one place.
"""

from __future__ import annotations

import random
from typing import Any, Dict, List, Sequence, Tuple

from gen.histories import INVOKE, OK, Rec
from gen.list_append import list_append


def to_register(history: Sequence[Rec]) -> List[Rec]:
    """A list-append run read as a register run: appends become writes,
    a read's list its last element."""
    def mop(m: List[Any]) -> List[Any]:
        f, k, v = m
        if f == "append":
            return ["w", k, v]
        if v is None:                   # an invocation's read
            return [f, k, None]
        return [f, k, v[-1] if v else None]
    return [o._replace(value=[mop(m) for m in o.value]) for o in history]


def rw_register(config: Dict[str, Any], params: Dict[str, Any],
                seed: int) -> Dict[str, Any]:
    """The configuration's one history, relabeled by the seed."""
    run = list_append(config, params, seed)
    return {"keyed": False, "records": to_register(run["records"])}


GENERATORS = {"rw_register": rw_register}


# -- corruptors: one guarantee broken in one place ---------------------------

def _windows(history: Sequence[Rec]) -> Dict[int, Tuple[int, int]]:
    """Entry of each ok completion -> (its invocation's entry, itself)."""
    open_: Dict[Any, int] = {}
    out: Dict[int, Tuple[int, int]] = {}
    for i, o in enumerate(history):
        if o.type == INVOKE:
            open_[o.process] = i
        else:
            inv = open_.pop(o.process, -1)
            if o.type == OK:
                out[i] = (inv, i)
    return out


def _last_writes(history: Sequence[Rec]) -> Dict[Any, List[Tuple[int, Any]]]:
    """Key -> (ok completion's entry, the value it left) of each ok
    transaction that wrote the key, in history order."""
    out: Dict[Any, List[Tuple[int, Any]]] = {}
    for i, o in enumerate(history):
        if o.type == OK:
            last = {k: v for f, k, v in o.value if f == "w"}
            for k, v in last.items():
                out.setdefault(k, []).append((i, v))
    return out


def _with_read(history: Sequence[Rec], i: int, j: int, v: Any) -> List[Rec]:
    out = list(history)
    value = [list(m) for m in out[i].value]
    value[j][2] = v
    out[i] = out[i]._replace(value=value)
    return out


def _reads(history: Sequence[Rec], rng: random.Random,
           only: bool = False) -> List[Tuple[int, int]]:
    """(entry, micro-op) of ok reads of a key their transaction does not
    write, shuffled; ``only``: transactions of that one read alone."""
    out = [(i, j) for i, o in enumerate(history) if o.type == OK
           and (len(o.value) == 1 or not only)
           for j, (f, k, _) in enumerate(o.value)
           if f == "r" and not any(g == "w" and kk == k
                                   for g, kk, _ in o.value)]
    rng.shuffle(out)
    return out


def stale_read(history: Sequence[Rec], rng: random.Random) -> List[Rec]:
    """One ok read returns the value its key held before a write that
    completed before the reader was invoked: a value another ok
    transaction left there, which had completed before that writer was
    invoked.  Only the keys' realtime (linearizable) order places the two
    values, so only it shows the read stale."""
    win = _windows(history)
    writes = _last_writes(history)
    for i, j in _reads(history, rng):
        _, k, v = history[i].value[j]
        mine = writes.get(k, [])
        by = [w for w, x in mine if x == v]
        if v is None or len(by) != 1 or win[by[0]][1] >= win[i][0]:
            continue
        older = [x for w, x in mine if win[w][1] < win[by[0]][0] and x != v]
        if older:
            return _with_read(history, i, j, older[-1])
    raise ValueError("no read to make stale")


def future_read(history: Sequence[Rec], rng: random.Random) -> List[Rec]:
    """A transaction of one read returns a value of its key that an ok
    transaction wrote, and left, after being invoked once the reader had
    completed: a cycle of that write-read edge and the realtime order."""
    win = _windows(history)
    writes = _last_writes(history)
    for i, j in _reads(history, rng, only=True):
        k = history[i].value[j][1]
        later = [x for w, x in writes.get(k, []) if win[w][0] > i]
        if later:
            return _with_read(history, i, j, later[0])
    raise ValueError("no read to take from the future")


CORRUPTORS = {"stale_read": stale_read, "future_read": future_read}
