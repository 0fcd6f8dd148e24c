"""Per-key registers as a partition nemesis leaves them: the keyed history
of ``jepsen.tests.linearizable-register`` (``linearizable_register.clj:
18-53``) run under a partition, simulated.

What the source fixes and this keeps, per key: ``threads_per_key`` threads,
thread ``t`` on node ``t mod nodes``; the first ``nodes`` of them reserved
for reads (``gen/reserve n r``), the rest mixing write : cas : cas; the
key's length ``per_key_limit x (0.9 + rand 0.1)``; and ``process_limit``:
every ``info`` retires its process, the thread goes on under a fresh one,
and the key takes no further op once a process beyond the limit would be
needed (the repo's own ``gen.process_limit``: with 10 threads and a limit
of 20 that is the 11th crash; the ops in flight still complete).

What the nemesis adds: keys are run ``concurrent_keys`` side by side in key
order, and blocks of ``partition_block`` keys alternate between a healed
and a partitioned stretch.  A key that lived in a partition has
``minority_nodes`` of its nodes cut off, drawn from its seed; every op of a
thread on such a node times out.  A timed-out op holds its thread for as
long as ``timeout_ops`` ordinary ops of a thread take, then completes as
the suites' clients complete it: a read ``fail`` (idempotent), a write or
cas ``info``.

The simulation's rules are ``gen.histories.cas_register_history``'s: ticks
draw an invoke on a free thread or a step (effect, then completion) of a
pending op; an op crashes with probability ``crash_p`` at a step; a crashed
or timed-out write or cas takes effect with probability ``crash_apply_p``
at some tick from its invoke on.  So every lane is linearizable by
construction, before ``refute_every`` corrupts one read in some.

``history_seed`` fixes every lane's structure; ``--seed`` orders the lanes
and relabels each (``gen.histories.relabel``), so every seed is the same
search.  Plain tuples; imports nothing of the program.
"""

from __future__ import annotations

import random
from collections import Counter
from typing import Any, Dict, List, Optional, Sequence, Tuple

from gen.histories import FAIL, INFO, INVOKE, OK, Rec, corrupt_reads, relabel


def register_history(n_ops: int, threads: int, readers: int, values: int,
                     crash_p: float, crash_apply_p: float, process_limit: int,
                     cut_off: Sequence[int], timeout_ops: int,
                     rng: random.Random) -> List[Rec]:
    """One key's history: at most ``n_ops`` ops from ``threads`` threads, of
    which the first ``readers`` only read and the rest mix write : cas :
    cas; the threads in ``cut_off`` time out on every op.  A thread starts
    as the process of its own number; fresh processes count on from
    ``threads``."""
    state: Optional[int] = None
    history: List[Rec] = []
    process = list(range(threads))          # thread -> its process now
    next_process = threads
    free = list(range(threads))
    pending: Dict[int, Dict[str, Any]] = {}  # thread -> its op in flight
    waiting: Dict[int, Dict[str, Any]] = {}  # thread -> its timed-out op
    late_effects: List[Rec] = []
    live = [t for t in range(threads) if t not in cut_off]
    done_live = 0           # completions of threads that are not cut off
    invoked = 0
    open_for_ops = True     # False once process_limit has cut the key
    t = 0

    def apply(op: Rec) -> bool:
        nonlocal state
        if op.f == "write":
            state = op.value
        elif op.f == "cas":
            if state != op.value[0]:
                return False
            state = op.value[1]
        return True

    def crash(thread: int, d: Dict[str, Any], error: str) -> None:
        """An ``info``: it burns the thread's process, and the key closes
        once the fresh one is beyond the limit."""
        nonlocal next_process, open_for_ops
        history.append(Rec(process[thread], INFO, d["op"].f, None, t, error))
        open_for_ops = open_for_ops and next_process < process_limit
        process[thread] = next_process
        next_process += 1

    while (open_for_ops and invoked < n_ops) or pending or waiting:
        t += rng.randint(1, 1000)
        if late_effects and rng.random() < 0.3:
            apply(late_effects.pop(rng.randrange(len(late_effects))))
        # a timed-out op completes once the live threads have done
        # timeout_ops ops each since its invoke, or nothing else is left
        idle = not pending and not (open_for_ops and invoked < n_ops
                                    and free)
        due = [th for th, d in waiting.items()
               if idle or done_live >= d["due"]]
        if due:
            th = due[0]
            d = waiting.pop(th)
            if d["op"].f == "read":
                history.append(Rec(process[th], FAIL, "read", None, t,
                                   "timeout"))
            else:
                crash(th, d, "timeout")
            free.append(th)
            continue
        roll = rng.random()
        if free and open_for_ops and invoked < n_ops \
                and (roll < 0.45 or not pending):
            th = free.pop(rng.randrange(len(free)))
            if th < readers:
                op = Rec(process[th], INVOKE, "read", None, t)
            elif rng.randrange(3) == 0:
                op = Rec(process[th], INVOKE, "write",
                         rng.randrange(values), t)
            else:
                op = Rec(process[th], INVOKE, "cas",
                         [rng.randrange(values), rng.randrange(values)], t)
            history.append(op)
            invoked += 1
            if th in cut_off:
                waiting[th] = {"op": op,
                               "due": done_live + timeout_ops * len(live)}
                if op.f != "read" and rng.random() < crash_apply_p:
                    late_effects.append(op)
            else:
                pending[th] = {"op": op, "effected": False}
        elif pending:
            th = rng.choice(list(pending))
            d = pending[th]
            if rng.random() < crash_p:
                if not d["effected"] and d["op"].f != "read" \
                        and rng.random() < crash_apply_p:
                    late_effects.append(d["op"])
                crash(th, d, "crashed")
            elif not d["effected"]:
                op = d["op"]
                d["result"] = (OK, state) if op.f == "read" else \
                    (OK if apply(op) else FAIL, op.value)
                d["effected"] = True
                continue
            else:
                history.append(Rec(process[th], d["result"][0], d["op"].f,
                                   d["result"][1], t))
            del pending[th]
            free.append(th)
            done_live += 1
    return history


def lane_stats(history: Sequence[Rec]) -> Tuple[int, int, int, int]:
    """(ops, ``info`` ops, peak of the checker's pending window, processes)
    of one key's history.  In the window an ``ok`` op is pending from its
    invoke to its completion and a crashed write or cas for ever; a failed
    op and a crashed read never enter it (the checker drops them)."""
    fate: Dict[int, str] = {}
    open_at: Dict[Any, int] = {}
    for i, o in enumerate(history):
        if o.type == INVOKE:
            open_at[o.process] = i
        else:
            fate[open_at.pop(o.process)] = o.type
    peak = now = 0
    for i, o in enumerate(history):
        if o.type == INVOKE:
            now += fate[i] == OK or (fate[i] == INFO and o.f != "read")
            peak = max(peak, now)
        elif o.type == OK:
            now -= 1
    return (len(fate), sum(f == INFO for f in fate.values()), peak,
            len({o.process for o in history}))


def describe(lanes: Sequence[Sequence[Rec]], threads: int,
             process_limit: int) -> List[str]:
    """What a run logs of its lanes: the histograms of ops, ``info`` ops and
    peak pending per key, and the keys ``process_limit`` cut (their crashes
    outran the fresh processes the limit leaves)."""
    stats = [lane_stats(h) for h in lanes]

    def hist(i: int, width: int = 1) -> str:
        c = Counter(s[i] // width * width for s in stats)
        return " ".join(f"{k}:{c[k]}" for k in sorted(c))
    cut = sum(threads + s[1] > process_limit for s in stats)
    return [f"nemesis_keyed: {len(lanes)} keys, "
            f"{sum(s[0] for s in stats)} ops, {cut} keys cut by "
            f"process_limit {process_limit}",
            f"nemesis_keyed: ops per key, by 20 (ops:keys) {hist(0, 20)}",
            f"nemesis_keyed: info ops per key {hist(1)}",
            f"nemesis_keyed: peak pending per key {hist(2)}"]


def refuted_lane(i: int, every: int, block: int) -> bool:
    """One lane in ``every`` has one corrupted read: the first of each run
    of ``every``, and in every other run the first that lived in a
    partition, so that both kinds of lane are refuted."""
    if not every:
        return False
    shift = block if (i // every) % 2 and block < every else 0
    return i % every == shift


def keyed_nemesis(config: Dict[str, Any], params: Dict[str, Any],
                  seed: int) -> Dict[str, Any]:
    """``keys`` registers in one history, values wrapped as ``(key,
    value)``; lane ``i`` is simulated from ``history_seed + i`` and lived in
    a partition when ``(i // partition_block) % 2 == 1``.  The seed orders
    the lanes and relabels each; ``concurrent_keys`` lanes run side by side,
    each on its own ``process_stride`` process ids, merged by time."""
    values, n_keys = int(config["values"]), int(config["keys"])
    nodes, threads = int(config["nodes"]), int(config["threads_per_key"])
    limit, plimit = int(config["per_key_limit"]), int(config["process_limit"])
    lo, hi = config["per_key_limit_factor"]
    base, block = int(params["history_seed"]), int(params["partition_block"])
    every = int(params.get("refute_every", 0))
    lanes = []
    for i in range(n_keys):
        rng = random.Random(base + i)
        n_ops = int(limit * (lo + rng.random() * (hi - lo)))
        minority = rng.sample(range(nodes), int(params["minority_nodes"])) \
            if (i // block) % 2 else []
        h = register_history(
            n_ops, threads, nodes, values, float(params["crash_p"]),
            float(params["crash_apply_p"]), plimit,
            [t for t in range(threads) if t % nodes in minority],
            int(params["timeout_ops"]), rng)
        if refuted_lane(i, every, block):
            h = corrupt_reads(h, n=1, seed=base + i, values=values)
        lanes.append(h)
    for line in describe(lanes, threads, plimit):
        print(line, flush=True)
    rng = random.Random(seed)
    rng.shuffle(lanes)
    stride = int(params["process_stride"])
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


GENERATORS = {"keyed_nemesis": keyed_nemesis}
