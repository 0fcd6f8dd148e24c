"""Transactional cycle workloads — generators + Elle-equivalent checkers.

Parity: jepsen.tests.cycle / cycle.append / cycle.wr (the thin adapters at
jepsen/src/jepsen/tests/cycle/append.clj:11-46 and wr.clj:9-25): generators
emit micro-op transactions; the checkers are ``checker.elle``'s, the
anomaly inference of jepsen_tpu.elle with its cycle search on the device
(jepsen_tpu.elle_tpu) wherever one is present.
"""

from __future__ import annotations

import math
import random
from typing import Any, Dict, Optional

from jepsen_tpu import generator as gen
from jepsen_tpu.checker.elle import ElleListAppend, ElleRwRegister


class KeyPool:
    """Elle's rotating pool of active keys (``elle.list-append/wr-txns``):
    ``key_count`` keys take the traffic at a time, a key is retired after
    ``max_writes_per_key`` writes and the next fresh key takes its slot, so
    a read's list never outgrows that many elements.  ``key_dist``
    ``exponential`` picks slot ``i`` with weight ``key_dist_base ** i``,
    ``uniform`` any slot alike.  Without ``max_writes_per_key`` no key is
    ever retired (the fixed key set of this port's first callers)."""

    def __init__(self, key_count: int,
                 max_writes_per_key: Optional[int] = None,
                 key_dist: str = "uniform", key_dist_base: float = 2.0,
                 rng=random):
        if key_dist not in ("exponential", "uniform"):
            raise ValueError(f"unknown key_dist {key_dist!r}")
        self.active = list(range(key_count))
        self.next_key = key_count
        self.writes: Dict[Any, int] = {}
        self.max_writes = max_writes_per_key
        self.exponential = key_dist == "exponential"
        self.base = key_dist_base
        self.scale = ((key_dist_base ** key_count - 1) * key_dist_base
                      / (key_dist_base - 1))
        self.rng = rng

    def slot(self) -> int:
        if not self.exponential:
            return self.rng.randrange(len(self.active))
        x = self.rng.random() * self.scale + self.base
        return min(len(self.active) - 1,
                   int(math.log(x) / math.log(self.base)) - 1)

    def read_key(self):
        return self.active[self.slot()]

    def write(self):
        """(key, the key's next value); retires the key when it is full."""
        i = self.slot()
        k = self.active[i]
        v = self.writes[k] = self.writes.get(k, 0) + 1
        if self.max_writes is not None and v >= self.max_writes:
            self.active[i] = self.next_key
            self.next_key += 1
        return k, v


def _txn_gen(write_f: str, keys: int, min_len: int, max_len: int,
             read_p: float, pool_kw: Dict[str, Any]):
    pool = KeyPool(pool_kw.pop("key_count", None) or keys, **pool_kw)

    def one():
        txn = []
        for _ in range(pool.rng.randint(min_len, max_len)):
            if pool.rng.random() < read_p:
                txn.append(["r", pool.read_key(), None])
            else:
                txn.append([write_f, *pool.write()])
        return {"f": "txn", "value": txn}

    return gen.FnGen(one)


def append_gen(keys: int = 8, min_len: int = 1, max_len: int = 4,
               read_p: float = 0.5, **pool_kw):
    """Random list-append transactions with per-key unique values.
    ``pool_kw``: :class:`KeyPool`'s ``key_count`` (default ``keys``),
    ``max_writes_per_key``, ``key_dist``, ``key_dist_base``, ``rng``."""
    return _txn_gen("append", keys, min_len, max_len, read_p, pool_kw)


def wr_gen(keys: int = 8, min_len: int = 1, max_len: int = 4,
           read_p: float = 0.5, **pool_kw):
    return _txn_gen("w", keys, min_len, max_len, read_p, pool_kw)


#: ``append.clj``'s checker, under the name this port first gave it: the
#: device-tier Elle checker (``elle-tpu`` when a device is present, the
#: same host search through ``elle_tpu.finish_lane`` when none is).
AppendChecker = ElleListAppend
WrChecker = ElleRwRegister


def append_workload(keys: int = 8, consistency_models=None,
                    **kw) -> Dict[str, Any]:
    """``consistency_models`` mirrors append.clj:15-21: validity is judged
    against the requested models (e.g. ``("snapshot-isolation",)`` passes
    write-skew; ``("strict-serializable",)`` adds the realtime order); the
    elle-style ``not``/``also-not`` boundary is reported either way."""
    return {"generator": append_gen(keys, **kw),
            "checker": ElleListAppend(consistency_models=consistency_models)}


def wr_workload(keys: int = 8, consistency_models=None,
                sequential_keys: bool = False,
                linearizable_keys: Optional[bool] = None,
                **kw) -> Dict[str, Any]:
    """wr.clj:9-25: ``consistency_models`` as :func:`append_workload`'s;
    ``sequential_keys`` and ``linearizable_keys`` are elle's per-key
    version-order assumptions, ``linearizable_keys`` by default on exactly
    where a strict model is asked for (it implies them)."""
    return {"generator": wr_gen(keys, **kw),
            "checker": ElleRwRegister(
                consistency_models=consistency_models,
                sequential_keys=sequential_keys,
                linearizable_keys=linearizable_keys)}
