"""The offline loop: one user, one history, ``core.analyze`` again and again.

Set-up (imports, the chip, the compile cache, inputs from the seed, one
warm-up call of the cell's own history) ends where the window starts; the
window is :func:`harness.window.run_window` over ``core.analyze`` and
nothing else in the process; then the peak memory is read, the per-layer
readers read (traced run only), and the plain reference decides ``correct``.

From the program this file takes the system under test and nothing else:
``core.analyze`` with the checker the traffic file's ``entry`` names, the
``Op``/``History`` types its entry wants, ``ops.cache``'s one cache rule,
and for the probes the layer functions that the traffic file names.
"""

from __future__ import annotations

import gc
import importlib
import os
import shutil
import time
from typing import Any, Callable, Dict, List, Optional

from gen.histories import GENERATORS, split_keys
from harness import correct, device, report, trace as tr
from harness.manifest import ROOT, Cell, plugin
from harness.window import run_window


def _resolve(dotted: str) -> Any:
    """``package.module:attr`` -> (module, attr name)."""
    mod, attr = dotted.split(":")
    return importlib.import_module(mod), attr


def program_history(records: List[Any]) -> Any:
    """The program's own input type, from the benchmark's plain records."""
    from jepsen_tpu.history import History, Op
    return History([Op(process=r.process, type=r.type, f=r.f, value=r.value,
                       time=r.time, error=r.error) for r in records],
                   reindex=True)


def program_checker(entry: str, model_name: str) -> Any:
    """The checker a user would put in the test map for this entry."""
    from jepsen_tpu import independent
    from jepsen_tpu.checker.linearizable import linearizable
    from jepsen_tpu.models import get_model
    inner = linearizable(get_model(model_name))
    if entry == "linearizable":
        return inner
    if entry == "independent.linearizable":
        return independent.checker(inner)
    raise ValueError(f"unknown entry {entry!r}")


class Spans:
    """Host-clock spans around package attributes the traffic file names,
    each also a ``TraceAnnotation`` on the profiler's clock.  Traced run
    only; the attribute is restored on exit."""

    def __init__(self, targets: Dict[str, str]) -> None:
        self.targets = targets
        self.seconds: Dict[str, float] = {k: 0.0 for k in targets}
        self._undo: List[Any] = []

    def __enter__(self) -> "Spans":
        import jax
        for name, dotted in self.targets.items():
            mod, attr = _resolve(dotted)
            inner = getattr(mod, attr)

            def wrapped(*a: Any, _inner: Callable = inner, _name: str = name,
                        **kw: Any) -> Any:
                t0 = time.monotonic()
                with jax.profiler.TraceAnnotation(tr.SPAN_PREFIX + _name):
                    try:
                        return _inner(*a, **kw)
                    finally:
                        self.seconds[_name] += time.monotonic() - t0
            setattr(mod, attr, wrapped)
            self._undo.append((mod, attr, inner))
        return self

    def __exit__(self, *exc: Any) -> None:
        for mod, attr, inner in self._undo:
            setattr(mod, attr, inner)


def probes(traffic: Dict[str, Any], history: Any, model_name: str
           ) -> Dict[str, Callable[[], Dict[str, float]]]:
    """Host-side layer calls the ``host_timer`` reader may time, outside
    the window: each returns the seconds it took and the entries it
    covered.  The per-key sub-histories are made once and shared."""
    from jepsen_tpu import independent
    from jepsen_tpu.checker.prep import prepare as prep
    from jepsen_tpu.models import get_model
    subs: List[Any] = []
    split_s: List[float] = []

    def split() -> Dict[str, float]:
        if not split_s:
            t0 = time.monotonic()
            subs.extend(independent.subhistory(k, history)
                        for k in independent.history_keys(history))
            split_s.append(time.monotonic() - t0)
        return {"seconds": split_s[0],
                "ops": float(sum(len(s) for s in subs))}

    def prepare() -> Dict[str, float]:
        if traffic["entry"].startswith("independent"):
            split()
        hs = subs or [history]
        model = get_model(model_name)
        t0 = time.monotonic()
        for h in hs:
            prep(h, model)
        return {"seconds": time.monotonic() - t0,
                "ops": float(sum(len(h) for h in hs))}

    return {"split": split, "prepare": prepare}


def reference_verdicts(cell: Cell, gen: Dict[str, Any], **kw: Any
                       ) -> Dict[Any, Dict[str, Any]]:
    """The configuration's plain reference over the records: key -> verdict
    (``None`` for a single history)."""
    check = plugin("reference", cell.config["reference"], "check")
    if gen["keyed"]:
        return {k: check(recs, **kw)
                for k, recs in split_keys(gen["records"]).items()}
    return {None: check(gen["records"], **kw)}


class GcWatch:
    """Collections that took over 2 ms while it is on, as (generation,
    seconds): so that a stall in the window can be told from the
    collector's."""

    def __init__(self) -> None:
        self.log: List[Any] = []
        self._t = 0.0

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._t = time.monotonic()
        elif time.monotonic() - self._t > 0.002:
            self.log.append((info["generation"],
                             round(time.monotonic() - self._t, 4)))

    def __enter__(self) -> "GcWatch":
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc: Any) -> None:
        gc.callbacks.remove(self._on_gc)


def window(call: Callable[[], Dict[str, Any]], seconds: float,
           spans: Spans, trace_dir: Optional[str]) -> Dict[str, Any]:
    """The measured window; with ``trace_dir`` under the profiler, each call
    and the window a span on the profiler's clock."""
    if trace_dir is None:
        return run_window(call, seconds)
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0         # spans and device ops only
    jax.profiler.start_trace(trace_dir, profiler_options=options)

    def traced_call() -> Dict[str, Any]:
        with jax.profiler.TraceAnnotation(tr.SPAN_PREFIX + "call"):
            return call()
    try:
        with spans, jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            return run_window(traced_call, seconds)
    finally:
        jax.profiler.stop_trace()


def run(cell: Cell, seed: int, seconds: float, traced: bool,
        t_start: float, log: report.Log,
        require_chip: Callable[[int], Dict[str, Any]] = device.require_tpu
        ) -> int:
    traffic, config = cell.traffic, cell.config
    analyzers = config["device_analyzers"]
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
    keyed = gen["keyed"]
    history = program_history(gen["records"])
    test = {"name": cell.name,
            "checker": program_checker(traffic["entry"], config["model"])}
    setup["inputs"] = time.monotonic() - t
    log.say(f"cell {cell.name} seed {seed} seconds {seconds} trace "
            f"{int(traced)} device {stamp} cache {cache_dir or 'off'} "
            f"history {len(history)} entries")

    def call() -> Dict[str, Any]:
        return core.analyze(test, history)

    t = time.monotonic()
    warm = call()
    setup["warmup_call"] = time.monotonic() - t
    at_setup = watch.mark()
    setup["backend_compile_or_load"] = at_setup["backend_compile_s"]
    warm_configs = correct.configs_explored(warm, keyed)
    shapes = sorted(compile_hist_stats())
    gc.collect()
    setup_s = time.monotonic() - t_start
    log.say("set-up breakdown s: " + ", ".join(
        f"{k} {v:.3f}" for k, v in setup.items())
        + f"; total {setup_s:.3f} (process start to window start)")
    log.say(f"set-up compile events: {at_setup}")
    log.say(f"warm-up call: valid {warm.get('valid')} configs-explored "
            f"{warm_configs}; engine shapes {len(shapes)}: {shapes}")

    # -- the window ----------------------------------------------------------
    spans = Spans(traffic.get("spans", {}) if traced else {})
    trace_dir = None
    if traced:
        seconds = min(seconds, float(traffic.get("trace_seconds", seconds)))
        trace_dir = os.path.join(ROOT, "store", "bench", cell.name,
                                 f"trace-{seed}")
    with GcWatch() as gcs:
        win = window(call, seconds, spans, trace_dir)
    in_window = watch.mark()
    memory = device.memory_stats(cell.chips)
    stamp = dict(stamp, memory_peak_bytes=int(
        memory.get("peak_bytes_in_use", 0)))
    results = win.pop("results")
    explored = [correct.configs_explored(r, keyed) for r in results]
    for i, res in enumerate(results):
        log.say(f"call {i}: {win['call_walls_s'][i]:.4f} s valid "
                f"{res.get('valid')} configs-explored {explored[i]}")
    log.say(f"window: {win['wall_s']:.4f} s over {win['calls']} calls = "
            f"{win['per_call_s']:.4f} s a verdict; compile events in the "
            f"window: {in_window}; collections over 2 ms (generation, s): "
            f"{gcs.log}")

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
                "host_answers": sum(correct.host_answers(r, keyed, analyzers)
                                    for r in results),
                "configs_explored": sum(explored)},
            "events": {"setup": at_setup, "window": in_window},
            "spans": spans.seconds, "window_s": win["wall_s"],
            "probes": probes(traffic, history, config["model"]),
            "trace": dtrace, "memory": memory, "log": log}
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
    t = time.monotonic()
    want = reference_verdicts(cell, gen)
    verdict = correct.compare(results, want, keyed, warm_configs, analyzers)
    log.say(f"reference: {len(want)} verdict(s), "
            f"{sum(not w['valid'] for w in want.values())} refuted, in "
            f"{time.monotonic() - t:.3f} s; correct {verdict['correct']}")
    log.write(os.path.join(ROOT, "store", "bench", cell.name,
                           f"seed{seed}-trace{int(traced)}.log"))
    report.finish(report.result_line(
        verdict["correct"], verdict["attempted"], verdict["failed"], metrics,
        stamp, verdict["compared"], breakdown), verdict["compared"])
    return 0
