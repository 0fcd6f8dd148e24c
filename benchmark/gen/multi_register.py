"""One multi-key register's history: the operations of the reference's
multi-key workload (``yugabyte/src/yugabyte/multi_key_acid.clj``: a few
keys, small values, a write upserts a random non-empty subset of the keys
in one transaction, a read observes a random non-empty subset, a nil read
is always legal), simulated as ``gen.histories.cas_register_history``
simulates one register.

A copy, draw for draw, of the program's ``synth.multi_register_history``
(so that a later PR may change ``synth.py`` and cannot change the traffic),
in plain :class:`gen.histories.Rec` tuples; imports nothing of the program.

``history_seed`` fixes the structure; ``--seed`` draws one relabeling from
its symmetries: a permutation of the value alphabet (the ``v`` of every
``[k, v]`` pair; ``None`` stays), a permutation of the keys (every ``k``;
pairs sorted by key again, as the generator emits them) and a renaming of
the processes.  ``gen.histories.relabel`` cannot serve: it sends every
small int inside a list through the value permutation, keys included.
"""

from __future__ import annotations

import random
from typing import Any, Dict, List, Optional, Sequence

from gen.histories import INFO, INVOKE, OK, Rec


def multi_register_history(n_ops: int, keys: int = 3, concurrency: int = 5,
                           values: int = 5, crash_p: float = 0.003,
                           seed: int = 0, read_p: float = 0.5) -> List[Rec]:
    """``n_ops`` multi-key reads and writes against a key -> value map,
    simulated: invokes, effects and completions interleave freely, so the
    history is linearizable by construction.  A read invokes with
    ``[[k, None], ...]`` and completes ``ok`` with what it observed
    (``None`` for a key never written); a crashed op becomes ``info`` (a
    read's without a value), and half of the crashed writes that had not
    taken effect still do so later."""
    rng = random.Random(seed)
    state: Dict[int, int] = {}
    history: List[Rec] = []
    free = list(range(concurrency))
    pending: Dict[int, Dict[str, Any]] = {}
    ghost_effects: List[Dict[str, Any]] = []
    t = 0
    invoked = 0

    def subset() -> List[int]:
        return sorted(rng.sample(range(keys), rng.randint(1, keys)))

    while invoked < n_ops or pending:
        t += rng.randint(1, 1000)
        if ghost_effects and rng.random() < 0.3:
            ge = ghost_effects.pop(rng.randrange(len(ghost_effects)))
            state.update({k: v for k, v in ge["op"].value})
        roll = rng.random()
        if free and invoked < n_ops and (roll < 0.45 or not pending):
            p = free.pop(rng.randrange(len(free)))
            if rng.random() < read_p:
                op = Rec(p, INVOKE, "read", [[k, None] for k in subset()], t)
            else:
                op = Rec(p, INVOKE, "write",
                         [[k, rng.randrange(values)] for k in subset()], t)
            history.append(op)
            pending[p] = {"op": op, "effected": False, "result_value": None}
            invoked += 1
        elif pending:
            p = rng.choice(list(pending))
            d = pending[p]
            op = d["op"]
            if rng.random() < crash_p:
                history.append(Rec(p, INFO, op.f,
                                   op.value if op.f != "read" else None,
                                   t, "crashed"))
                if not d["effected"] and op.f != "read" \
                        and rng.random() < 0.5:
                    ghost_effects.append(d)
                del pending[p]
                free.append(p)
            elif not d["effected"]:
                if op.f == "read":
                    d["result_value"] = [[k, state.get(k)]
                                         for k, _ in op.value]
                else:
                    state.update({k: v for k, v in op.value})
                    d["result_value"] = op.value
                d["effected"] = True
            else:
                history.append(Rec(p, OK, op.f, d["result_value"], t))
                del pending[p]
                free.append(p)
    return history


def relabel(history: Sequence[Rec], rng: random.Random, keys: int,
            values: int) -> List[Rec]:
    """One draw from the history's symmetries: the values, the keys and the
    processes renamed.  Linearizability, the refuting op's position and the
    size of the search are unchanged by it.  A value outside the alphabet
    (``None``, a corrupted read's) stays what it is."""
    vperm = list(range(values))
    rng.shuffle(vperm)
    kperm = list(range(keys))
    rng.shuffle(kperm)
    procs = sorted({o.process for o in history})
    names = list(procs)
    rng.shuffle(names)
    rename = dict(zip(procs, names))

    def pairs(value: Optional[List[List[Any]]]) -> Any:
        if value is None:
            return None
        return sorted([kperm[k], vperm[v] if isinstance(v, int)
                       and 0 <= v < values else v] for k, v in value)

    return [o._replace(process=rename[o.process], value=pairs(o.value))
            for o in history]


def multi_register(config: Dict[str, Any], params: Dict[str, Any],
                   seed: int) -> Dict[str, Any]:
    """The configuration's one history, relabeled by the seed."""
    keys, values = int(config["register_keys"]), int(config["values"])
    recs = multi_register_history(
        int(config["ops"]), keys=keys, concurrency=int(config["concurrency"]),
        values=values, crash_p=float(params["crash_p"]),
        seed=int(params["history_seed"]), read_p=float(config["read_p"]))
    return {"keyed": False,
            "records": relabel(recs, random.Random(seed), keys, values)}


GENERATORS = {"multi_register": multi_register}
