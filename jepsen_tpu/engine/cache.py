"""The bounded compiled-engine cache — one LRU for every device engine.

Every device engine in the stack (the single-history wgl driver, the
vmapped batch engine, megabatch's grouped runners) pins jitted
executables whose size scales with window*capacity*chunk; a service that
sees many shapes would grow an unbounded dict without end.  One shared
LRU keeps the hot buckets resident across *all* consumers — the bucket
ladder (engine/ladder.py) bounds the key universe, this cache bounds the
resident set — and its hit/miss/eviction counters feed the serve metrics
endpoint (an eviction storm means the ladder is too fine).

Key discipline: entries key on (tag, model name, model variant, shape
components...), never on closure identity, so every ``get_model()`` call
reuses one compiled engine.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Any, Dict


class EngineCache:
    """Bounded compiled-engine cache (thread-safe LRU)."""

    def __init__(self, capacity: int):
        self.capacity = max(1, int(capacity))
        self._d: "OrderedDict[Any, Any]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.group_reuses = 0

    def get(self, key, group_reuse: bool = False):
        """``group_reuse=True`` marks a lookup made for an additional
        dispatch group within ONE logical batch (check_batch's >512-lane
        split, megabatch's grouped vmap): a found entry counts toward
        ``group_reuses`` instead of ``hits``, so the hit rate keeps
        measuring cross-call cache effectiveness rather than being
        inflated by same-dispatch reuse."""
        with self._lock:
            if key in self._d:
                self._d.move_to_end(key)
                if group_reuse:
                    self.group_reuses += 1
                else:
                    self.hits += 1
                return self._d[key]
            self.misses += 1
            return None

    def put(self, key, value):
        with self._lock:
            self._d[key] = value
            self._d.move_to_end(key)
            while len(self._d) > self.capacity:
                self._d.popitem(last=False)
                self.evictions += 1
            return value

    def __len__(self):
        return len(self._d)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            tags: Dict[str, int] = {}
            for key in self._d:
                tag = key[0] if isinstance(key, tuple) and key else "?"
                tags[str(tag)] = tags.get(str(tag), 0) + 1
            return {"size": len(self._d), "capacity": self.capacity,
                    "hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions,
                    "group_reuses": self.group_reuses,
                    "tags": tags}


#: The shared engine cache: batch/single/megabatch runners all live here
#: (distinct key tags), so one knob bounds total pinned executables.
CACHE = EngineCache(int(os.environ.get("JEPSEN_TPU_ENGINE_CACHE", "32")))


def engine_cache_stats() -> Dict[str, Any]:
    """Hit/miss/eviction counters of the compiled-engine cache (a miss is
    a fresh trace+compile — the serve metrics' recompile counter), plus
    a per-tag resident count so the "singlev"/"batchv"/"megav" key
    families are all visible on the metrics surface."""
    return CACHE.stats()
