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

from jepsen_tpu.clock import mono_now
from jepsen_tpu.obs.recorder import RECORDER, span

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


# -- first use ----------------------------------------------------------------
#
# What a process pays once.  JAX announces every program it traces, lowers
# and compiles (or loads from the persistent cache) through
# ``jax.monitoring``; the listeners below sum those seconds for the process,
# by phase and by who asked (an engine's first call, or an eager op), and
# fire at no other time: a steady call pays nothing.

#: JAX's event -> the phase it is summed under.  ``load`` is
#: ``compile_or_get_cached``: a compile on a miss, the disk read and the
#: deserialisation on a hit.
_PHASE_OF = {"/jax/core/compile/jaxpr_trace_duration": "trace",
             "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
             "/jax/core/compile/backend_compile_duration": "load"}
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

_FIRST_USE_LOCK = threading.Lock()
_LISTENING = False


def _zero_first_use() -> Dict[str, Any]:
    return {"trace_s": 0.0, "lower_s": 0.0, "load_s": 0.0, "programs": 0,
            "cache_hits": 0, "cache_misses": 0, "retrieval_s": 0.0,
            "engine_s": 0.0, "eager_s": 0.0, "eager_programs": 0,
            "by_shape": {}, "eager_by_fun": {},
            "first_calls": 0, "first_call_s": 0.0,
            "analyze_calls": 0, "analyze_first_s": 0.0,
            "analyze_later_s": 0.0}


#: the keys of :func:`first_use_stats` (a contract: tests/test_first_use.py,
#: docs/observability.md, the benchmark's ``program_sums`` reader)
FIRST_USE_KEYS = tuple(_zero_first_use())

_FIRST_USE = _zero_first_use()


class _Thread(threading.local):
    """This thread's open JAX events, outermost first, as ``[phase, fun,
    seconds of the events nested in it, cache]``, and the shapes of its open
    ``compile.first_call`` spans."""

    def __init__(self) -> None:
        self.open: List[List[Any]] = []
        self.shapes: List[str] = []


_THREAD = _Thread()


def _shape_sums(shape: str) -> Dict[str, Any]:
    return _FIRST_USE["by_shape"].setdefault(
        shape, {"trace_s": 0.0, "lower_s": 0.0, "load_s": 0.0,
                "first_call_s": 0.0, "cache": None})


def _fun_of(name: str) -> str:
    """``run_at`` from the trace event's ``run_at`` and from the lower and
    backend events' module name, ``jit(run_at)`` (``jit_run_at`` in older
    JAX)."""
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]
    return name.removeprefix("jit_")


def _on_enter(event: str, _value: Any, fun_name: str = "", **_: Any) -> None:
    """``log_elapsed_time`` records a scalar under the event's own name as
    it opens: that is how a nested event is told from an outermost one."""
    phase = _PHASE_OF.get(event)
    if phase is not None:
        _THREAD.open.append([phase, fun_name, 0.0, None])


def _on_cache_event(event: str, **_: Any) -> None:
    cache = _CACHE_EVENTS.get(event)
    if cache is None:
        return
    open_, shapes = _THREAD.open, _THREAD.shapes
    if open_:
        open_[-1][3] = cache
    with _FIRST_USE_LOCK:
        _FIRST_USE["cache_hits" if cache == "hit" else "cache_misses"] += 1
        if shapes:
            # one miss among a shape's programs and the shape missed
            sums = _shape_sums(shapes[-1])
            if sums["cache"] != "miss":
                sums["cache"] = cache


def _on_exit(event: str, secs: float, fun_name: str = "", **_: Any) -> None:
    """An event closes: its own seconds (those of the events nested in it
    taken out, so a ``jnp`` function traced inside ``run_at`` or an eager
    op compiled inside a trace is counted once) go to its phase, under the
    shape of the open ``compile.first_call`` or else under the outermost
    event's function as an eager program."""
    if event == _CACHE_RETRIEVAL:
        with _FIRST_USE_LOCK:
            _FIRST_USE["retrieval_s"] += secs
        return
    phase = _PHASE_OF.get(event)
    if phase is None:
        return
    open_ = _THREAD.open
    nested, cache = 0.0, None
    if open_ and open_[-1][0] == phase:     # else the enter was not heard
        _, _, nested, cache = open_.pop()
    own = max(secs - nested, 0.0)
    fun = _fun_of(open_[0][1] if open_ else fun_name)
    outermost = all(f[0] != phase for f in open_)
    if open_:
        open_[-1][2] += secs
    shape = _THREAD.shapes[-1] if _THREAD.shapes else None
    with _FIRST_USE_LOCK:
        s = _FIRST_USE
        programs = int(phase == "load")
        s[phase + "_s"] += own
        s["programs"] += programs
        if shape is not None:
            s["engine_s"] += own
            _shape_sums(shape)[phase + "_s"] += own
        else:
            s["eager_s"] += own
            s["eager_programs"] += programs
            by_fun = s["eager_by_fun"].setdefault(
                fun, {"programs": 0, "s": 0.0})
            by_fun["programs"] += programs
            by_fun["s"] += own
    if outermost and RECORDER.enabled:
        # known only once over: an event placed by its duration under the
        # open span, and no TraceAnnotation
        args = {"fun": fun, "shape": shape}
        if phase == "load":
            args["cache"] = cache
        RECORDER.record("span", "compile." + phase, t=mono_now() - secs,
                        dur_s=secs, args=args)


def listen_first_use() -> None:
    """Register the listeners above with ``jax.monitoring``, once a
    process: at the first :func:`timed_first_call` wrap or
    ``init_compilation_cache()``, whichever comes first."""
    global _LISTENING
    with _FIRST_USE_LOCK:
        if _LISTENING:
            return
        _LISTENING = True
    import jax.monitoring as mon
    mon.register_scalar_listener(_on_enter)
    mon.register_event_listener(_on_cache_event)
    mon.register_event_duration_secs_listener(_on_exit)


def observe_analyze(seconds: float) -> None:
    """One ``core.analyze`` took ``seconds``: the process's first call
    apart from the sum of the later ones."""
    with _FIRST_USE_LOCK:
        s = _FIRST_USE
        s["analyze_calls"] += 1
        if s["analyze_calls"] == 1:
            s["analyze_first_s"] = seconds
        else:
            s["analyze_later_s"] += seconds


def first_use_stats() -> Dict[str, Any]:
    """Sums over the process (:data:`FIRST_USE_KEYS`), in seconds and
    counts.  ``trace_s``, ``lower_s``, ``load_s``: each JAX event's own
    time, so they add up to the host time spent building programs;
    ``programs``: programs compiled or loaded; ``cache_hits``,
    ``cache_misses``, ``retrieval_s``: the persistent cache's.  By who
    asked: ``engine_s`` (events under an open ``compile.first_call``) and
    ``eager_s`` (any other), which sum to the three phases;
    ``eager_programs``; ``by_shape``: ``COMPILES``' key -> ``trace_s``,
    ``lower_s``, ``load_s``, ``first_call_s``, ``cache`` (``hit``, ``miss``
    or ``None``: no persistent cache); ``eager_by_fun``: function name ->
    ``programs``, ``s``.  ``first_calls``, ``first_call_s``: what the
    ``compile.first_call`` spans took (``first_call_s - engine_s`` is a
    first call's cost beyond JAX's three phases).  ``analyze_calls``,
    ``analyze_first_s``, ``analyze_later_s``: ``core.analyze``'s own
    wall, the first call apart from the sum of the later ones."""
    with _FIRST_USE_LOCK:
        out = dict(_FIRST_USE)
        for k in ("by_shape", "eager_by_fun"):
            out[k] = {name: dict(v) for name, v in out[k].items()}
        return out


def reset_first_use_stats() -> None:
    with _FIRST_USE_LOCK:
        _FIRST_USE.update(_zero_first_use())


def timed_first_call(fn, name: str):
    """Wrap a jitted callable so its *first* invocation — the one that
    pays XLA compilation — is timed into the compile histogram ``name``
    and ``first_use_stats()["by_shape"][name]`` and, as a
    ``compile.first_call`` span, the flight recorder and the profiler;
    what JAX traces, lowers and loads meanwhile is that shape's.  Later
    calls go straight through with one list-lookup of overhead.  The
    build sites (wgl/batch/megabatch cache misses) apply this to the
    callable they cache, so the histogram measures real compile latency
    per cache bucket key, not just host-side trace/wrap time."""
    listen_first_use()
    fired: List[bool] = []

    def first_timed(*args, **kwargs):
        if fired:
            return fn(*args, **kwargs)
        _THREAD.shapes.append(name)
        try:
            with span("compile.first_call", shape=name):
                t0 = mono_now()
                out = fn(*args, **kwargs)
                dt = mono_now() - t0
        finally:
            _THREAD.shapes.pop()
        fired.append(True)
        observe_compile(name, dt)
        with _FIRST_USE_LOCK:
            _FIRST_USE["first_calls"] += 1
            _FIRST_USE["first_call_s"] += dt
            _shape_sums(name)["first_call_s"] += dt
        return out

    return first_timed
