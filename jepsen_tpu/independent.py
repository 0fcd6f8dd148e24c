"""Lift single-key workloads over a space of keys.

Parity: jepsen.independent (jepsen/src/jepsen/independent.clj): ops carry
``(key, value)`` tuples; generators run one key at a time
(sequential_generator) or k keys across disjoint thread groups
(concurrent_generator, independent.clj:213-239); the checker splits the
history per key and checks each sub-history (independent.clj:266-317).
The split is one pass over the history (:func:`subhistories`), whatever
the number of keys.

TPU-first difference: when the sub-checker is a device-tier linearizable
checker, the per-key sub-histories are checked as ONE vmapped batch sharded
over the mesh (jepsen_tpu.parallel.check_batch) instead of a bounded pmap of
independent solver runs — the per-key independence the reference exploits
for CPU parallelism maps directly onto the ``data`` mesh axis.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

from jepsen_tpu import generator as gen
from jepsen_tpu.checker.core import Checker, UNKNOWN, check_safe, merge_valid
from jepsen_tpu.checker.linearizable import Linearizable
from jepsen_tpu.history import History, INVOKE, NEMESIS, Op
from jepsen_tpu.obs.recorder import carry, span

KeyedValue = Tuple[Any, Any]

#: host-tier per-key check parallelism when nothing else configures it
DEFAULT_WORKERS = 8


def worker_count(test: Optional[Dict[str, Any]] = None,
                 explicit: Optional[int] = None) -> int:
    """Resolve the per-key checking thread count: an explicit argument
    wins, then the test map's ``independent_workers`` opt, then the
    ``JEPSEN_TPU_WORKERS`` env var, then :data:`DEFAULT_WORKERS`."""
    for v in (explicit,
              (test or {}).get("independent_workers"),
              os.environ.get("JEPSEN_TPU_WORKERS")):
        if v:
            return max(1, int(v))
    return DEFAULT_WORKERS


def tuple_(k, v) -> KeyedValue:
    """A keyed value (independent.clj:21)."""
    return (k, v)


def key_of(op: Op) -> Optional[Any]:
    v = op.value
    if isinstance(v, tuple) and len(v) == 2:
        return v[0]
    return None


def rewrap_tuples(history: History) -> History:
    """Restore keyed-value tuples on a deserialized history: JSON has no
    tuple type, so a stored independent-workload history comes back with
    ``[k, v]`` lists that :func:`key_of` (correctly) refuses to treat as
    keys — an unkeyed cas value ``[old, new]`` is also a 2-element list,
    so the caller must *assert* the independent shape explicitly (the
    ``submit --independent`` flag / the web API's ``independent`` key)."""
    return History(
        [op.with_(value=tuple(op.value))
         if (op.process != NEMESIS and isinstance(op.value, list)
             and len(op.value) == 2) else op
         for op in history], reindex=True)


def history_keys(history: History) -> List[Any]:
    """All keys in the history, in first-appearance order
    (independent.clj:240)."""
    seen = []
    ss = set()
    for op in history:
        k = key_of(op)
        if k is not None and k not in ss:
            ss.add(k)
            seen.append(k)
    return seen


def subhistory(k, history: History) -> History:
    """The sub-history of key ``k``, values unwrapped
    (independent.clj:252)."""
    out = []
    for op in history:
        kk = key_of(op)
        if kk is None and op.process == NEMESIS:
            out.append(op)  # nemesis ops apply to every key's timeline
        elif kk == k:
            out.append(op.with_(value=op.value[1]))
    return History(out, reindex=True)


def subhistories(history: History) -> Dict[Any, History]:
    """Every key's sub-history from one pass over ``history``, keys in
    first-appearance order: equal, op for op and index for index, to
    ``{k: subhistory(k, history) for k in history_keys(history)}``."""
    subs: Dict[Any, List[Op]] = {}
    nemesis: List[Op] = []  # keyless nemesis ops so far: a later key's prefix
    for op in history:
        k = key_of(op)
        if k is not None:
            ops = subs.get(k)
            if ops is None:
                ops = subs[k] = [o.with_(index=i)
                                 for i, o in enumerate(nemesis)]
            ops.append(op.with_(value=op.value[1], index=len(ops)))
        elif op.process == NEMESIS:
            nemesis.append(op)
            for ops in subs.values():
                ops.append(op.with_(index=len(ops)))
    return {k: History.adopt(ops) for k, ops in subs.items()}


class _WrapKey(gen.Generator):
    """Wrap an inner generator's op values as (key, value)."""

    def __init__(self, k, inner):
        self.k = k
        self.inner = gen.lift(inner)

    def op(self, test, ctx):
        if self.inner is None:
            return None
        r = self.inner.op(test, ctx)
        if r is None:
            return None
        v, g2 = r
        if v is gen.PENDING:
            return (gen.PENDING, _WrapKey(self.k, g2))
        v = v.with_(value=(self.k, v.value))
        return (v, _WrapKey(self.k, g2) if g2 is not None else None)

    def update(self, test, ctx, event):
        if self.inner is None:
            return self
        k = key_of(event)
        if k == self.k:
            event = event.with_(value=event.value[1])
            return _WrapKey(self.k, self.inner.update(test, ctx, event))
        return self


def sequential_generator(keys: Iterable[Any],
                         fgen: Callable[[Any], Any]) -> gen.Generator:
    """One key at a time: when key k's generator exhausts, move to the next
    (independent.clj:31)."""
    return gen.Concat([_WrapKey(k, fgen(k)) for k in keys])


class ConcurrentGenerator(gen.Generator):
    """k keys at once, each owning a disjoint group of n threads
    (independent.clj:213-239): when a key's generator exhausts, its thread
    group moves on to the next unclaimed key."""

    def __init__(self, n: int, keys: Sequence[Any],
                 fgen: Callable[[Any], Any]):
        self.n = n
        self.keys = list(keys)
        self.fgen = fgen
        self.active: Dict[int, Optional[gen.Generator]] = {}  # group -> gen
        self.next_key = 0
        self.rr = 0  # round-robin cursor for same-time candidate ties

    def _clone(self):
        c = ConcurrentGenerator.__new__(ConcurrentGenerator)
        c.n = self.n
        c.keys = self.keys
        c.fgen = self.fgen
        c.active = dict(self.active)
        c.next_key = self.next_key
        c.rr = self.rr
        return c

    def _groups(self, ctx) -> List[List[Any]]:
        threads = [t for t in ctx.all_threads() if t != NEMESIS]
        return [threads[i:i + self.n]
                for i in range(0, len(threads) - len(threads) % self.n, self.n)]

    def _ensure(self, c, gi):
        if gi not in c.active:
            if c.next_key < len(c.keys):
                k = c.keys[c.next_key]
                c.next_key += 1
                c.active[gi] = _WrapKey(k, c.fgen(k))
            else:
                c.active[gi] = None

    def op(self, test, ctx):
        # Draw a CANDIDATE op from every group and dispense the soonest
        # (generator.clj `any`'s rule).  Returning the first group's op
        # starved the others whenever an outer pacing wrapper (stagger)
        # kept group 0's threads free at each draw: with k keys only the
        # first thread-group ever ran, so whole nodes had no clients.
        # Non-chosen groups keep their pre-draw state (no op was taken);
        # pending continuations ARE kept (they carry timer anchors).
        c = self._clone()
        groups = self._groups(ctx)
        pending = False
        cands = []  # (v, g2, gi)
        for gi, threads in enumerate(groups):
            while True:
                self._ensure(c, gi)
                g = c.active[gi]
                if g is None:
                    break
                r = g.op(test, ctx.restrict(threads))
                if r is None:
                    # group's key exhausted: advance to next key
                    del c.active[gi]
                    if c.next_key >= len(c.keys):
                        c.active[gi] = None
                        break
                    continue
                v, g2 = r
                if v is gen.PENDING:
                    pending = True
                    c.active[gi] = g2
                    break
                cands.append((v, g2, gi))
                break
        if cands:
            # Soonest op wins; ties (the common case — unpaced gens stamp
            # ops "now") rotate round-robin so no group monopolizes draws.
            tmin = min(v.time for v, _, _ in cands)
            ng = max(1, len(groups))
            v, g2, gi = min((cand for cand in cands if cand[0].time == tmin),
                            key=lambda cand: (cand[2] - c.rr) % ng)
            c.rr = (gi + 1) % ng
            if g2 is None:
                # key exhausted via a final (op, None) draw (limit's
                # shape): free the group so the next draw advances it
                # to the next unclaimed key instead of parking forever
                del c.active[gi]
            else:
                c.active[gi] = g2
            return (v, c)
        if pending:
            return (gen.PENDING, c)
        if all(g is None for g in c.active.values()) and \
                c.next_key >= len(c.keys):
            return None
        return (gen.PENDING, c)

    def update(self, test, ctx, event):
        t = ctx.process_thread(getattr(event, "process", None))
        if t is None or t == NEMESIS:
            return self
        c = self._clone()
        for gi, threads in enumerate(self._groups(ctx)):
            if t in threads and c.active.get(gi) is not None:
                c.active[gi] = c.active[gi].update(
                    test, ctx.restrict(threads), event)
                break
        return c


def concurrent_generator(n: int, keys: Sequence[Any],
                         fgen: Callable[[Any], Any]) -> gen.Generator:
    return ConcurrentGenerator(n, keys, fgen)


class IndependentChecker(Checker):
    """Split the history per key; check each sub-history
    (independent.clj:266-317).  Device-tier linearizable sub-checkers batch
    all keys into one vmapped engine call (optionally mesh-sharded)."""

    def __init__(self, inner: Checker, mesh=None,
                 max_workers: Optional[int] = None):
        self.inner = inner
        self.mesh = mesh
        # None = resolve at check time (test opts / JEPSEN_TPU_WORKERS env)
        self.max_workers = max_workers

    def check(self, test, history, opts=None):
        with span("entry.split", entries=len(history)) as sp:
            subs = subhistories(history)
            keys = list(subs)
            sp.set(keys=len(keys))
        results: Dict[Any, Dict[str, Any]] = {}

        inner = self.inner
        # only the pure-device algorithms take the batched engine; an
        # explicit host algorithm stays off the device, and "competition"
        # must race host+device per key rather than be hijacked
        # (checker.clj:199-202's algorithm switch semantics)
        wants_device = isinstance(inner, Linearizable) and \
            inner.algorithm in (None, "tpu")
        if wants_device and inner._jax_model() is not None:
            from jepsen_tpu.parallel import check_batch
            jm = inner._jax_model()
            rs = check_batch(jm, [subs[k] for k in keys], mesh=self.mesh,
                             **{k: v for k, v in inner.engine_opts.items()
                                if k in ("capacity", "max_capacity", "chunk")})
            results = dict(zip(keys, rs))
            # A refuted key is searched once.  The batch's answer carries the
            # refuting op; the host oracle re-derives the witness on the
            # failing prefix and linear.svg lands in the key's own result
            # dir (the reference's per-key result dirs + knossos render,
            # independent.clj:266-317, checker.clj:207-211).  The batched
            # pass paid for the search; this pays only for the evidence.
            for k, r in results.items():
                if r.get("valid") is False:
                    self._explain(test, subs[k], r,
                                  self._key_opts(opts, k), k)
        else:
            mw = worker_count(test, self.max_workers)
            with ThreadPoolExecutor(max_workers=mw) as ex:
                futs = {k: ex.submit(carry(check_safe), inner, test,
                                     subs[k], self._key_opts(opts, k))
                        for k in keys}
                # Merge in first-appearance key order regardless of which
                # future lands first: the results map (and everything
                # derived from it downstream) is deterministic for a given
                # history, independent of thread scheduling.
                results = {k: futs[k].result() for k in keys}

        bad = {k: r for k, r in results.items() if r.get("valid") is not True}
        out = {"valid": merge_valid([r.get("valid")
                                     for r in results.values()]),
               "key-count": len(keys),
               "results": results,
               "failures": sorted(bad, key=repr)}
        # Engine disagreement is a framework bug signal: surface it beside
        # `failures` so nobody has to scan per-key result maps to notice a
        # batch refutation the host witness didn't confirm.
        disagreements = sorted((k for k, r in results.items()
                                if "recheck" in r), key=repr)
        if disagreements:
            out["disagreements"] = disagreements
        return out

    def _explain(self, test, sub: History, r: Dict[str, Any], opts,
                 k) -> None:
        """Host confirmation and render of one batch-refuted key, in place.
        Nothing here softens the refutation, which exhaustive search earned:
        a witness search that ran out of budget or crashed degrades the
        witness alone, and a host oracle that finds the failing prefix
        linearizable (the two engines disagree: a framework bug) is noted
        under ``recheck`` while ``valid: False`` and its ``op`` stand."""
        with span("entry.rederive", key=k) as sp:
            try:
                self.inner.explain_refutation(test, sub, r, opts)
            except Exception as e:  # noqa: BLE001
                r.setdefault("witness",
                             {"error": f"witness search crashed: {e}"})
            w = r.get("witness")
            sp.set(confirmed=w is not None and w.get("valid") is False)
        if w is not None and "error" not in w \
                and w.get("valid") is not False:
            r["recheck"] = {"valid": w.get("valid"),
                            "note": "host witness did not confirm; "
                                    "batch refutation stands"}

    @staticmethod
    def _key_opts(opts, k):
        """Per-key result dir under independent/<key>/ so sub-checker
        artifacts (linear.svg, timelines) never collide across keys."""
        d = (opts or {}).get("store_dir")
        if not d:
            return opts
        kd = os.path.join(d, "independent", str(k))
        try:
            os.makedirs(kd, exist_ok=True)
        except OSError:
            return opts
        return {**opts, "store_dir": kd}


def checker(inner: Checker, mesh=None) -> Checker:
    return IndependentChecker(inner, mesh=mesh)
