"""Log-bucketed latency and compile-time histograms.

Buckets are powers of two, like the rungs of the engine's shape ladder:
an observation of ``s`` seconds lands in the bucket whose upper bound is
the smallest power of two of microseconds >= ``s``.  That keeps the
bucket universe bounded (a 64-second tail is ~36 rungs from the 1 µs
floor), makes histograms from different processes mergeable by plain
bucket-wise addition (every process has the identical ladder), and means
a compile-time histogram keyed by an engine-cache bucket key reports
quantiles over exactly the shapes the compile cache distinguishes.

Percentiles are cumulative-walk upper bounds: ``p99`` is the upper edge
of the first bucket at or past the 99th percentile of the count mass —
conservative (never under-reports) and exact enough at pow2 resolution
for dashboard work.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Iterable, List, Optional

#: histogram floor: one microsecond
_FLOOR_US = 1


def _bucket_of(us: int) -> int:
    b = _FLOOR_US
    while b < us:
        b *= 2
    return b


class Histogram:
    """One unlocked log-bucketed histogram (callers hold the set lock)."""

    __slots__ = ("buckets", "count", "sum_s")

    def __init__(self) -> None:
        self.buckets: Dict[int, int] = {}
        self.count = 0
        self.sum_s = 0.0

    def observe(self, seconds: float) -> None:
        us = int(seconds * 1e6)
        b = _bucket_of(us)
        self.buckets[b] = self.buckets.get(b, 0) + 1
        self.count += 1
        self.sum_s += max(seconds, 0.0)

    def merge_counts(self, buckets: Dict[int, int], count: int,
                     sum_s: float) -> None:
        for b, n in buckets.items():
            self.buckets[b] = self.buckets.get(b, 0) + n
        self.count += count
        self.sum_s += sum_s

    def percentile(self, p: float) -> float:
        """Upper bound of the bucket at the ``p``-th percentile, in
        seconds (0.0 for an empty histogram)."""
        if self.count <= 0:
            return 0.0
        target = p / 100.0 * self.count
        seen = 0
        for b in sorted(self.buckets):
            seen += self.buckets[b]
            if seen >= target:
                return b / 1e6
        return max(self.buckets) / 1e6  # pragma: no cover - defensive

    def snapshot(self) -> Dict[str, Any]:
        return {"count": self.count,
                "sum-s": round(self.sum_s, 6),
                "p50": self.percentile(50),
                "p90": self.percentile(90),
                "p99": self.percentile(99),
                "buckets-us": {str(b): self.buckets[b]
                               for b in sorted(self.buckets)}}


class HistogramSet:
    """A thread-safe named family of histograms (the unit Metrics and
    the compile sites observe into, and the unit scrapes merge)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._hists: Dict[str, Histogram] = {}

    def observe(self, name: str, seconds: float) -> None:
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = Histogram()
            h.observe(seconds)

    def snapshot(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            return {name: h.snapshot()
                    for name, h in sorted(self._hists.items())}


#: process-wide count of malformed per-histogram entries dropped by
#: ``merge_hist_snapshots`` — silently skipping a worker's corrupt
#: histogram is the right availability call (a scrape must not fail
#: because one worker was mid-crash), but the drop has to be visible
#: somewhere, so it lands in every ``Metrics.snapshot()``'s counters as
#: ``hist-merge-skipped``.  Whole-snapshot ``None`` (the "worker
#: unreachable" convention) is NOT counted: that is the protocol, not
#: corruption.
_MERGE_LOCK = threading.Lock()
_MERGE_SKIPPED = 0


def _note_merge_skip(n: int = 1) -> None:
    global _MERGE_SKIPPED
    with _MERGE_LOCK:
        _MERGE_SKIPPED += n


def merge_skipped_count() -> int:
    with _MERGE_LOCK:
        return _MERGE_SKIPPED


def merge_hist_snapshots(
        snaps: Iterable[Optional[Dict[str, Dict[str, Any]]]],
) -> Dict[str, Dict[str, Any]]:
    """Bucket-wise merge of ``HistogramSet.snapshot()`` documents from
    several processes into one fleet-wide document.  Identical ladders
    make the merge exact; malformed entries are skipped — and counted
    (``merge_skipped_count``) — so a scrape neither fails because one
    worker was mid-crash nor hides that its data was dropped."""
    merged: Dict[str, Histogram] = {}
    for snap in snaps:
        if not isinstance(snap, dict):
            continue
        for name, s in snap.items():
            if not isinstance(s, dict):
                _note_merge_skip()
                continue
            try:
                buckets = {int(b): int(n)
                           for b, n in (s.get("buckets-us") or {}).items()}
                count = int(s.get("count", 0))
                sum_s = float(s.get("sum-s", 0.0))
            except (TypeError, ValueError):
                _note_merge_skip()
                continue
            h = merged.get(name)
            if h is None:
                h = merged[name] = Histogram()
            h.merge_counts(buckets, count, sum_s)
    return {name: h.snapshot() for name, h in sorted(merged.items())}


#: process-wide compile/build histograms, one per engine-cache bucket
#: key family — global like the engine cache itself, surfaced through
#: every Metrics.snapshot() in the process
COMPILES = HistogramSet()


def observe_compile(name: str, seconds: float) -> None:
    COMPILES.observe(name, seconds)


def compile_hist_stats() -> Dict[str, Dict[str, Any]]:
    return COMPILES.snapshot()


def compile_event_count() -> int:
    """Total compile events observed process-wide — the numerator of the
    steady-state ``compiles-per-1k-dispatches`` gauge, and the number
    the megabatch CI smoke asserts goes flat once the ladder is warm."""
    return sum(int(s.get("count", 0))
               for s in COMPILES.snapshot().values())


#: process-wide monitor epoch-wall histograms, one per
#: ``monitor-epoch:<kind>:<stream>`` family — global like the monitors
#: themselves (they outlive any one service), surfaced through every
#: Metrics.snapshot() next to the compile histograms.  The stream bench
#: reads these to assert per-epoch wall stays flat in history length.
MONITOR_EPOCHS = HistogramSet()


def observe_monitor_epoch(name: str, seconds: float) -> None:
    MONITOR_EPOCHS.observe(name, seconds)


def monitor_epoch_hist_stats() -> Dict[str, Dict[str, Any]]:
    return MONITOR_EPOCHS.snapshot()


def timed_first_call(fn, name: str):
    """Wrap a jitted callable so its *first* invocation — the one that
    pays XLA compilation — is timed into the compile histogram ``name``
    and, as a ``compile.first_call`` span, the flight recorder and the
    profiler.  Later calls go straight through with one
    list-lookup of overhead.  The build sites (wgl/batch/megabatch
    cache misses) apply this to the callable they cache, so the
    histogram measures real compile latency per cache bucket key, not
    just host-side trace/wrap time."""
    fired: List[bool] = []

    def first_timed(*args, **kwargs):
        if fired:
            return fn(*args, **kwargs)
        from jepsen_tpu.clock import mono_now
        from jepsen_tpu.obs.recorder import span
        with span("compile.first_call", shape=name):
            t0 = mono_now()
            out = fn(*args, **kwargs)
            dt = mono_now() - t0
        fired.append(True)
        observe_compile(name, dt)
        return out

    return first_timed
