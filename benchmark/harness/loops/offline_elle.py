"""The offline loop for a transactional history under an anomaly checker:
one user, one history, ``core.analyze`` again and again, as
``harness.loops.offline`` has it for a register under ``linearizable``.

What differs is what a verdict is.  There is no refuting op and no
configuration count: the result is Elle's map (``valid``, ``anomaly-types``,
the device's four ``device-flags``, ``count``), and the plain reference
(``reference/elle_list_append.py``) gives the same four things, so
``correct`` compares those.  The checker is the one a suite's test map would
hold: the traffic file's ``entry`` (``package.module:function``) called with
the configuration's ``consistency_models``, its ``"checker"``.

The cell's one history is valid, so every answer of the window is "valid,
no flag": a program that built no realtime layer, ran no closure or read
zeros back would give it too.  So after the window the same checker, at
the shape the window ran (the same ``n_pad``, the realtime layer on),
answers the traffic file's ``probes``: the run's own history with one
guarantee broken in one place by a ``CORRUPTORS`` entry of the generator's
module.  The reference says what each must give, and ``correct`` counts the
probe's ``valid`` and ``device-flags`` with the window's.  A probe runs under
``probes.budget_s``: the host's recovery of a witness among 10,000
transactions is minutes of Python and not what the cell measures, so it is
cut short, and a probe's verdict may be ``unknown`` with
``cycle-search-truncated`` where the reference says ``False``; never
``True``.

A third of a call is the host's own Python, and the collector's full
passes were a third of that: with the run's 20,000-entry history and every
imported module alive, each pass walked some 230,000 objects to free none
of them, three passes in two calls, and the window's mean moved with
whichever calls they fell into.  So the window runs with what set-up left
alive frozen (``gc.freeze``): the collector stays on and collects what the
calls themselves allocate, and no longer walks the harness's own input.

``requires`` in the traffic file is checked before anything is generated,
as in ``offline_requires``: a program whose append workload is still the
host's cycle search would take hours over this history, and exits non-zero
at once instead.  The window, the profiler, the spans, the collector's
watch, the trace's reduction, the chip and the report are ``offline``'s and
the harness's, unchanged.
"""

from __future__ import annotations

import contextlib
import gc
import importlib
import os
import random
import shutil
import time
from typing import (Any, Callable, Dict, Iterator, List, Optional, Sequence,
                    Tuple)

from gen.histories import GENERATORS
from harness import correct, device, report, trace as tr
from harness.loops import offline_plug, offline_requires
from harness.loops.offline import GcWatch, Spans, program_history, window
from harness.manifest import ROOT, Cell, plugin

STRICT = "strict-serializable"


@contextlib.contextmanager
def frozen_heap() -> Iterator[None]:
    """Everything alive now (the inputs, the modules, the compiled
    programs' wrappers) out of the collector's sight until the block ends."""
    gc.freeze()
    try:
        yield
    finally:
        gc.unfreeze()


def program_checker(entry: str, models: Sequence[str]) -> Any:
    module, attr = entry.split(":")
    workload = getattr(importlib.import_module(module), attr)
    return workload(consistency_models=tuple(models))["checker"]


def host_answers(res: Dict[str, Any], analyzers: Sequence[str]) -> int:
    """``correct.host_answers`` and, besides, a search the host cut short."""
    return correct.host_answers(res, False, analyzers) \
        + bool(res.get("cycle-search-truncated"))


COUNTS = ("verdict_mismatches", "anomaly_mismatches", "flag_mismatches",
          "unknown_verdicts", "host_answers", "txn_count_drift")


def judge(res: Dict[str, Any], want: Dict[str, Any],
          warm_count: Optional[int], analyzers: Sequence[str],
          decided: Callable[[Any], set], budgeted: bool = False
          ) -> Dict[str, int]:
    """One result against the reference's ``want``, as the six counts.
    ``decided`` maps a checker's anomaly types onto those the reference
    names (``reference.decided``); where the reference finds a cycle and can
    name none, the checker has to name one of those the reference leaves
    unnamed.  ``budgeted``: the result is a probe's, its recovery cut short
    on purpose; ``unknown`` with ``cycle-search-truncated`` then stands for
    the reference's ``False`` (never for ``True``), the types a cut search
    found are not compared, and the cut is no host answer."""
    n = dict.fromkeys(COUNTS, 0)
    cut = budgeted and bool(res.get("cycle-search-truncated"))
    valid = res.get("valid")
    if cut and valid == "unknown":
        n["verdict_mismatches"] += want["valid"] is not False
    elif valid not in (True, False):
        n["unknown_verdicts"] += 1
    elif valid is not want["valid"]:
        n["verdict_mismatches"] += 1
    types = set(res.get("anomaly-types") or ())
    if not cut and (decided(types) != set(want["anomaly_types"])
                    or (want["unnamed_cycle"] and types == decided(types))):
        n["anomaly_mismatches"] += 1
    if res.get("device-flags") != want["flags"]:
        n["flag_mismatches"] += 1
    n["host_answers"] += (correct.host_answers(res, False, analyzers)
                          if budgeted else host_answers(res, analyzers))
    if res.get("count") != want["count"] or (
            warm_count is not None and res.get("count") != warm_count):
        n["txn_count_drift"] += 1
    return n


def compare(results: List[Dict[str, Any]], want: Dict[str, Any],
            warm_count: Optional[int], analyzers: Sequence[str],
            decided: Callable[[Any], set],
            probes: Sequence[Tuple[Dict[str, Any], Dict[str, Any]]] = ()
            ) -> Dict[str, Any]:
    """Every result of the window against the reference's ``want``, and
    every probe's ``(result, want)`` pair: each comparison exact, each
    limit 0."""
    judged = [judge(res, want, warm_count, analyzers, decided)
              for res in results]
    judged += [judge(res, w, None, analyzers, decided, budgeted=True)
               for res, w in probes]
    compared = {}
    for k in COUNTS:
        v = sum(j[k] for j in judged)
        compared[k] = {"value": v, "limit": 0, "ok": v == 0}
    return {"correct": all(c["ok"] for c in compared.values())
            and bool(results),
            "attempted": len(judged),
            "failed": sum(any(j.values()) for j in judged),
            "compared": compared}


def probe_answers(test: Dict[str, Any], records: List[Any], seed: int,
                  traffic: Dict[str, Any], reference: Callable[..., Any],
                  realtime: bool, say: Callable[[str], None] = print
                  ) -> List[Tuple[Dict[str, Any], Dict[str, Any]]]:
    """``test``'s checker on each of the traffic file's ``probes`` (the
    run's history through one ``CORRUPTORS`` entry of the generator's
    module, under ``probes.budget_s``), beside the reference's answer on
    the same records: ``(result, want)`` pairs for :func:`compare`."""
    spec = traffic.get("probes", {})
    corruptors = plugin("gen", traffic["generator_module"], "CORRUPTORS")
    out = []
    for name in spec.get("corruptors", ()):
        t = time.monotonic()
        bad = corruptors[name](records, random.Random(seed))
        res = test["checker"].check(test, program_history(bad),
                                    {"budget_s": spec["budget_s"]})
        t_checked = time.monotonic()
        want = reference(bad, realtime=realtime)
        out.append((res, want))
        say(f"probe {name}: valid {res.get('valid')} analyzer "
            f"{res.get('analyzer')} flags {res.get('device-flags')} "
            f"truncated {bool(res.get('cycle-search-truncated'))} in "
            f"{t_checked - t:.3f} s; reference: valid {want['valid']} "
            f"types {want['anomaly_types']} flags {want['flags']} in "
            f"{time.monotonic() - t_checked:.3f} s")
    return out


def run(cell: Cell, seed: int, seconds: float, traced: bool,
        t_start: float, log: report.Log,
        require_chip: Callable[[int], Dict[str, Any]] = device.require_tpu
        ) -> int:
    traffic, config = cell.traffic, cell.config
    offline_requires.require(traffic["requires"])
    offline_plug.register(traffic)
    analyzers = config["device_analyzers"]
    models = config["consistency_models"]
    setup: Dict[str, float] = {}

    # -- set-up ------------------------------------------------------------
    t = time.monotonic()
    stamp = require_chip(cell.chips)
    from jepsen_tpu import core
    from jepsen_tpu.obs.hist import compile_hist_stats
    from jepsen_tpu.ops.cache import init_compilation_cache
    setup["imports_and_chip"] = time.monotonic() - t
    t = time.monotonic()
    cache_dir = init_compilation_cache()
    watch = device.CompileWatch()
    setup["cache_init"] = time.monotonic() - t
    t = time.monotonic()
    gen = GENERATORS[traffic["generator"]](config, traffic["params"], seed)
    history = program_history(gen["records"])
    test = {"name": cell.name,
            "checker": program_checker(traffic["entry"], models)}
    setup["inputs"] = time.monotonic() - t
    log.say(f"cell {cell.name} seed {seed} seconds {seconds} trace "
            f"{int(traced)} device {stamp} cache {cache_dir or 'off'} "
            f"history {len(history)} entries, models {models}")

    def call() -> Dict[str, Any]:
        return core.analyze(test, history)

    t = time.monotonic()
    warm = call()
    setup["warmup_call"] = time.monotonic() - t
    at_setup = watch.mark()
    setup["backend_compile_or_load"] = at_setup["backend_compile_s"]
    shapes = sorted(compile_hist_stats())
    gc.collect()
    setup_s = time.monotonic() - t_start
    log.say("set-up breakdown s: " + ", ".join(
        f"{k} {v:.3f}" for k, v in setup.items())
        + f"; total {setup_s:.3f} (process start to window start)")
    log.say(f"set-up compile events: {at_setup}")
    log.say(f"warm-up call: valid {warm.get('valid')} analyzer "
            f"{warm.get('analyzer')} count {warm.get('count')} flags "
            f"{warm.get('device-flags')}; engine shapes {len(shapes)}: "
            f"{shapes}")

    # -- the window ----------------------------------------------------------
    spans = Spans(traffic.get("spans", {}) if traced else {})
    trace_dir = None
    if traced:
        seconds = min(seconds, float(traffic.get("trace_seconds", seconds)))
        trace_dir = os.path.join(ROOT, "store", "bench", cell.name,
                                 f"trace-{seed}")
    with frozen_heap(), GcWatch() as gcs:
        win = window(call, seconds, spans, trace_dir)
    in_window = watch.mark()
    memory = device.memory_stats(cell.chips)
    stamp = dict(stamp, memory_peak_bytes=int(
        memory.get("peak_bytes_in_use", 0)))
    results = win.pop("results")
    for i, res in enumerate(results):
        log.say(f"call {i}: {win['call_walls_s'][i]:.4f} s valid "
                f"{res.get('valid')} analyzer {res.get('analyzer')} types "
                f"{res.get('anomaly-types')}")
    log.say(f"window: {win['wall_s']:.4f} s over {win['calls']} calls = "
            f"{win['per_call_s']:.4f} s a verdict; compile events in the "
            f"window: {in_window}; collections over 2 ms (generation, s): "
            f"{gcs.log}; device memory {memory}")

    # -- the metrics ---------------------------------------------------------
    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown: Optional[Dict[str, Any]] = None
    if traced:
        described: List[str] = []
        dtrace = tr.read_xplane(tr.find_xplane(trace_dir), cell.chips,
                                described)
        shutil.rmtree(trace_dir, ignore_errors=True)
        for line in described:
            log.say(line)
        log.say(f"trace: window {dtrace.window_s:.4f} s busy "
                f"{dtrace.busy_s:.4f} s launches {dtrace.launches} "
                f"op events {dtrace.n_op_events}")
        stamp.update(busy_s=dtrace.busy_s, window_s=dtrace.window_s)
        breakdown = {"device_ops": dtrace.top_ops(),
                     "idle_gaps": dtrace.idle_gaps()}
        ctx = {
            "counters": {
                "calls": win["calls"],
                "host_answers": sum(host_answers(r, analyzers)
                                    for r in results)},
            "events": {"setup": at_setup, "window": in_window},
            "spans": spans.seconds, "window_s": win["wall_s"],
            "probes": {}, "trace": dtrace, "memory": memory,
            "device": stamp, "log": log}
        for m in cell.per_layer():
            value = plugin("readers", m["reader"], "read")(ctx, **m["args"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        measured = {traffic["verdict_metric"]: win["per_call_s"],
                    "setup_s": setup_s}
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": measured[m["name"]],
                                  "unit": m["unit"]}

    # -- correct: the plain reference, once the window has closed ----------
    reference = plugin("reference", config["reference"], "check")
    realtime = STRICT in models
    t = time.monotonic()
    want = reference(gen["records"], realtime=realtime)
    log.say(f"reference: valid {want['valid']} types "
            f"{want['anomaly_types']} flags {want['flags']} count "
            f"{want['count']} in {time.monotonic() - t:.3f} s")
    probes = probe_answers(test, gen["records"], seed, traffic, reference,
                           realtime, log.say)
    after_probes = watch.mark()
    log.say(f"compile events during the probes: {after_probes}")
    verdict = compare(results, want, warm.get("count"), analyzers,
                      plugin("reference", config["reference"], "decided"),
                      probes)
    log.say(f"correct {verdict['correct']}: "
            f"{verdict['attempted'] - verdict['failed']} of "
            f"{verdict['attempted']} answers (window {len(results)}, "
            f"probes {len(probes)})")
    log.write(os.path.join(ROOT, "store", "bench", cell.name,
                           f"seed{seed}-trace{int(traced)}.log"))
    report.finish(report.result_line(
        verdict["correct"], verdict["attempted"], verdict["failed"], metrics,
        stamp, verdict["compared"], breakdown), verdict["compared"])
    return 0
