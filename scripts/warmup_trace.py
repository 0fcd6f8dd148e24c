#!/usr/bin/env python3
"""Why a process's first ``core.analyze`` costs more than its second: one
fresh process, one benchmark cell's history, the first call under the
profiler and the flight recorder, the second under the recorder alone.

  python3 scripts/warmup_trace.py --workload cas10k-clean.offline --seed 7

The history comes from the benchmark's own generator and files (imported
from ``benchmark/``, nothing copied).  Prints one JSON object, and writes it
to ``chiprun_out/warmup_trace.<cell>.<seed>.json``:

  ``spans``      per span name: count, seconds and self seconds (its direct
                 children taken out) in call 1 and in call 2, and call 1's
                 excess of each, largest excess of self time first: the self
                 times of a call add up to its wall, so this column says
                 where the first call's excess sits
  ``device``     call 1 on the device's clock (``benchmark/harness/trace.py:
                 read_xplane``: the program's spans are ``TraceAnnotation``s
                 there): busy and idle seconds and the idle gaps by the
                 innermost span they fell in; beside them call 2's
                 ``drivers.poll`` seconds, the busy time of a steady call.
                 A first ``drivers.poll`` that waits on a chip still loading
                 its program shows as idle under ``drivers.poll``; one that
                 waits on the host shows as idle under the host's span
  ``first_use``  ``obs.hist.first_use_stats()`` after call 1, and what call
                 2 added to it (``analyze_*`` alone, if nothing compiled)

Only on a TPU, as the benchmark: exits non-zero at once anywhere else.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
from typing import Any, Callable, Dict, List, Sequence

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

Events = Sequence[Dict[str, Any]]


def by_span(events: Events) -> Dict[str, List[float]]:
    """Recorder events -> name: [count, seconds, self seconds].  A span's
    self time is its duration less that of its direct children (the events
    that name it as parent), never under 0."""
    below: Dict[str, float] = {}
    for e in events:
        parent = e.get("parent-span-id")
        if parent is not None:
            below[parent] = below.get(parent, 0.0) + e.get("dur-s", 0.0)
    out: Dict[str, List[float]] = {}
    for e in events:
        dur = e.get("dur-s", 0.0)
        row = out.setdefault(e["name"], [0, 0.0, 0.0])
        row[0] += 1
        row[1] += dur
        row[2] += max(dur - below.get(e.get("span-id"), 0.0), 0.0)
    return out


def excess_by_span(first: Events, later: Events) -> Dict[str, Dict[str, Any]]:
    """Reduction (a): per span name ``count``, ``s`` and ``self_s`` as
    ``[call 1, call 2]``, and ``excess_s``, ``excess_self_s`` (call 1 less
    call 2), the largest ``excess_self_s`` first."""
    a, b = by_span(first), by_span(later)
    rows = {}
    for name in set(a) | set(b):
        (n1, s1, own1), (n2, s2, own2) = (a.get(name, [0, 0.0, 0.0]),
                                          b.get(name, [0, 0.0, 0.0]))
        rows[name] = {"count": [n1, n2], "s": [s1, s2],
                      "self_s": [own1, own2], "excess_s": s1 - s2,
                      "excess_self_s": own1 - own2}
    return dict(sorted(rows.items(),
                       key=lambda kv: -kv[1]["excess_self_s"]))


def moved(before: Dict[str, Any], after: Dict[str, Any]) -> Dict[str, Any]:
    """The keys of ``first_use_stats()`` that differ, numbers as
    differences, ``by_shape`` and ``eager_by_fun`` as the entries that
    changed."""
    out: Dict[str, Any] = {}
    for k, v in after.items():
        was = before.get(k)
        if v == was:
            continue
        if isinstance(v, dict):
            out[k] = {name: x for name, x in v.items()
                      if x != (was or {}).get(name)}
        else:
            out[k] = v - (was or 0)
    return out


def main(argv=None, require_chip: Callable[[int], Any] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="cas10k-clean.offline")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, ROOT]
    from gen.histories import GENERATORS
    from harness import device, trace as tr
    from harness.loops import offline, offline_plug
    from harness.manifest import Cell
    cell = Cell(args.workload)
    if "generator_module" in cell.traffic:  # a generator in a file of its own
        offline_plug.register(cell.traffic)
    (require_chip or device.require_tpu)(cell.chips)
    import jax

    from jepsen_tpu import core
    from jepsen_tpu.clock import mono_now
    from jepsen_tpu.obs.hist import first_use_stats
    from jepsen_tpu.obs.recorder import RECORDER
    from jepsen_tpu.ops.cache import init_compilation_cache
    init_compilation_cache()
    gen = GENERATORS[cell.traffic["generator"]](
        cell.config, cell.traffic["params"], args.seed)
    history = offline.program_history(gen["records"])
    test = {"name": cell.name,
            "checker": offline.program_checker(cell.traffic["entry"],
                                               cell.config["model"])}
    trace_dir = os.path.join(ROOT, "store", "bench", cell.name,
                             f"warmup-trace-{args.seed}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0         # spans and device ops only
    was_on = RECORDER.enabled
    RECORDER.enable()
    calls = []
    try:
        for traced in (True, False):
            RECORDER.clear()
            if traced:
                jax.profiler.start_trace(trace_dir, profiler_options=options)
            try:
                t0 = mono_now()
                with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
                    res = core.analyze(test, history)
                wall = mono_now() - t0
            finally:
                if traced:
                    jax.profiler.stop_trace()
            calls.append({"wall_s": wall, "valid": res.get("valid"),
                          "events": RECORDER.snapshot(),
                          "dropped": RECORDER.stats()["dropped"],
                          "first_use": first_use_stats()})
    finally:
        RECORDER.enabled = was_on
    first, later = calls
    dtrace = tr.read_xplane(tr.find_xplane(trace_dir), cell.chips)
    shutil.rmtree(trace_dir, ignore_errors=True)
    spans = excess_by_span(first["events"], later["events"])
    out = {
        "workload": cell.name, "seed": args.seed,
        "entries": len(history),
        "call_wall_s": [first["wall_s"], later["wall_s"]],
        "excess_s": first["wall_s"] - later["wall_s"],
        "valid": [first["valid"], later["valid"]],
        "recorder_dropped": [first["dropped"], later["dropped"]],
        "spans": spans,
        "device": {
            "call1_window_s": dtrace.window_s, "call1_busy_s": dtrace.busy_s,
            "call1_idle_s": dtrace.window_s - dtrace.busy_s,
            "call1_launches": dtrace.launches,
            "call1_op_events": dtrace.n_op_events,
            "call1_idle_gaps": dtrace.idle_gaps(40),
            "call2_poll_s": spans.get("drivers.poll", {"s": [0, 0.0]})["s"][1],
        },
        "first_use": {"after_call1": first["first_use"],
                      "moved_by_call2": moved(first["first_use"],
                                              later["first_use"])},
    }
    line = json.dumps(out)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(
            out_dir, f"warmup_trace.{cell.name}.{args.seed}.json"), "w",
            encoding="utf-8") as f:
        f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
