"""From the profiler's trace to numbers: device busy and idle time, each
op's own time, program launches, and the idle gaps by the harness span they
fell in.

The reductions work on plain ``(name, start_ns, duration_ns)`` triples, so
they are checked on a hand-made trace (``tests/test_benchmark.py``);
:func:`read_xplane` is the one place that knows the profiler's file.
"""

from __future__ import annotations

import glob
import os
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]            # name, start_ns, duration_ns

#: harness spans are ``jax.profiler.TraceAnnotation``s with this prefix
SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "window"


def union_ns(events: Iterable[Event], t0: float, t1: float
             ) -> Tuple[float, List[Tuple[float, float]]]:
    """Length of the union of the events' intervals clipped to [t0, t1],
    and the gaps between them as (start, length), the edges included."""
    iv = sorted((max(s, t0), min(s + d, t1)) for _, s, d in events
                if s < t1 and s + d > t0)
    busy = 0.0
    gaps: List[Tuple[float, float]] = []
    edge = t0
    for a, b in iv:
        if a > edge:
            gaps.append((edge, a - edge))
        if b > edge:
            busy += b - max(a, edge)
            edge = b
    if t1 > edge:
        gaps.append((edge, t1 - edge))
    return busy, gaps


def self_times_ns(events: Sequence[Event]) -> Dict[str, float]:
    """Each op's own time by name: its duration less that of the ops nested
    in it (a ``while`` holds its body's ops on the same line)."""
    out: Dict[str, float] = {}
    stack: List[List[Any]] = []             # [name, end, self]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            n, _, own = stack.pop()
            out[n] = out.get(n, 0.0) + own
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([name, s + d, d])
    for n, _, own in stack:
        out[n] = out.get(n, 0.0) + own
    return out


def op_name(event_name: str) -> str:
    """``sort.16`` from the TPU plane's ``%sort.16 = (...) sort(...)``: the
    op's HLO name without its text."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def innermost_span(t: float, spans: Sequence[Event]) -> str:
    """Name of the shortest harness span that holds instant ``t``."""
    best: Optional[Event] = None
    for sp in spans:
        if sp[1] <= t < sp[1] + sp[2] and (best is None or sp[2] < best[2]):
            best = sp
    return best[0][len(SPAN_PREFIX):] if best else "outside"


class DeviceTrace:
    """One traced window reduced: everything the trace readers need."""

    def __init__(self, ops_by_chip: Sequence[Sequence[Event]],
                 modules_by_chip: Sequence[Sequence[Event]],
                 spans: Sequence[Event]) -> None:
        win = [sp for sp in spans if sp[0] == WINDOW_SPAN]
        every = [e for line in ops_by_chip for e in line]
        if win:
            self.t0, self.t1 = win[0][1], win[0][1] + win[0][2]
        elif every:
            self.t0 = min(e[1] for e in every)
            self.t1 = max(e[1] + e[2] for e in every)
        else:
            self.t0 = self.t1 = 0.0
        self.window_s = (self.t1 - self.t0) / 1e9
        chips = max(1, len(ops_by_chip))
        busy = 0.0
        gaps: List[Tuple[float, float]] = []
        self.op_self_s: Dict[str, float] = {}
        self.n_op_events = 0
        for line in ops_by_chip:
            inside = [e for e in line
                      if e[1] < self.t1 and e[1] + e[2] > self.t0]
            self.n_op_events += len(inside)
            b, g = union_ns(inside, self.t0, self.t1)
            busy += b
            gaps += g
            for name, own in self_times_ns(inside).items():
                self.op_self_s[name] = self.op_self_s.get(name, 0.0) \
                    + own / 1e9 / chips
        self.busy_s = busy / 1e9 / chips
        self.launches = sum(
            1 for line in modules_by_chip for e in line
            if self.t0 <= e[1] < self.t1) / chips
        self.spans = [sp for sp in spans if sp[0] != WINDOW_SPAN]
        self._gaps = gaps

    def top_ops(self, n: int = 10) -> List[List[Any]]:
        top = sorted(self.op_self_s.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v] for k, v in top]

    def idle_gaps(self, n: int = 10) -> List[List[Any]]:
        """All idle time by the harness span its middle fell in
        (``sum:<span>``, largest first), then the longest single gaps
        (``gap:<span>``)."""
        named = [(innermost_span(s + d / 2, self.spans), d / 1e9)
                 for s, d in self._gaps]
        sums: Dict[str, float] = {}
        for name, d in named:
            sums[name] = sums.get(name, 0.0) + d
        out = [["sum:" + k, v]
               for k, v in sorted(sums.items(), key=lambda kv: -kv[1])]
        out = out[:n // 2]
        longest = sorted(named, key=lambda g: -g[1])[:n - len(out)]
        return out + [["gap:" + k, v] for k, v in longest]


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def read_xplane(path: str, chips: int = 1,
                describe: Optional[List[str]] = None) -> DeviceTrace:
    """The device planes' ``XLA Ops`` and ``XLA Modules`` lines and the
    host's harness spans, read with nothing but JAX.  ``describe`` collects
    one line per plane and line of the file, for the run's log."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: List[List[Event]] = []
    modules: List[List[Event]] = []
    spans: List[Event] = []
    for plane in data.planes:
        is_device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            events = None
            if is_device and line.name in ("XLA Ops", "XLA Modules"):
                events = [(op_name(e.name), e.start_ns, e.duration_ns)
                          for e in line.events]
                (ops if line.name == "XLA Ops" else modules).append(events)
            elif plane.name.startswith("/host:"):
                events = [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
                spans += events
            if describe is not None:
                n = len(events) if events is not None \
                    else sum(1 for _ in line.events)
                describe.append(f"plane {plane.name} line {line.name}: "
                                f"{n} events")
    return DeviceTrace(ops[:chips], modules[:chips], spans)
