"""The orchestrator: run a complete test end to end.

Parity: jepsen.core/run! (jepsen/src/jepsen/core.clj:322-401), composed of
the same phases with the same durability guarantees:

  prepare -> store.save_0 -> sessions -> OS setup -> DB setup ->
  client+nemesis setup -> interpreter run (history) -> store.save_1 ->
  analysis (checker) -> store.save_2 -> log snarfing -> teardown

Failures during analysis never lose the history (it hit disk in save_1);
a JVM-shutdown-hook's job (core.clj:143-163) is played by try/finally
blocks around log download and teardown.
"""

from __future__ import annotations

import logging
import time
import traceback
from typing import Any, Dict, Optional

from jepsen_tpu import control, db as jdb, nemesis as jnemesis, store
from jepsen_tpu import os as jos
from jepsen_tpu.checker.core import Checker, UNKNOWN, check_safe
from jepsen_tpu.clock import mono_now
from jepsen_tpu.generator import interpreter
from jepsen_tpu.history import History
from jepsen_tpu.obs.hist import observe_analyze
from jepsen_tpu.obs.recorder import span

logger = logging.getLogger("jepsen.core")


def prepare_test(test: Dict[str, Any]) -> Dict[str, Any]:
    """Fill defaults (core.clj:306-320 prepare-test)."""
    test.setdefault("name", "noname")
    test.setdefault("start_time", time.strftime("%Y%m%dT%H%M%S"))
    test.setdefault("nodes", [])
    concurrency = test.get("concurrency", 5)
    if isinstance(concurrency, str) and concurrency.endswith("n"):
        # "3n" syntax: multiple of node count (cli.clj:150-168)
        concurrency = int(concurrency[:-1] or 1) * max(1, len(test["nodes"]))
    test["concurrency"] = int(concurrency)
    return test


def run(test: Dict[str, Any]) -> Dict[str, Any]:
    """Run the test; returns it with :history and :results attached."""
    prepare_test(test)
    store.make_run_dir(test)
    log_handler = store.start_logging(test)
    logger.info("Running test %s", test["name"])
    try:
        store.save_0(test)
        mon = None
        if test.get("monitor"):
            # Online monitor (jepsen_tpu.monitor): taps the interpreter's
            # op stream via test["_monitor"], checks incrementally during
            # the run, and hands analyze() a resumable frontier.
            from jepsen_tpu.monitor import Monitor
            mon = Monitor.from_test(test)
            if mon is not None:
                test["_monitor"] = mon.start()
        has_cluster = bool(test.get("nodes"))
        if has_cluster:
            control.setup_sessions(test)
        try:
            _setup_os(test)
            _setup_db(test)
            try:
                history = _run_case(test)
            finally:
                # A stalled run (interpreter.StalledRun) still leaves its
                # salvaged partial history on disk — partial beats nothing
                # for post-mortem analysis.
                ph = test.get("partial_history")
                if ph is not None and "history" not in test:
                    try:
                        store.save_1(test, ph)
                    except Exception:  # noqa: BLE001
                        logger.exception("saving partial history")
                # Logs must come off the nodes BEFORE teardown wipes them
                # (core.clj:143-163 with-log-snarfing wraps the db phase).
                _snarf_logs_safe(test)
                _teardown_db(test, final=True)
            test["history"] = history
            store.save_1(test, history)
            if mon is not None:
                # Settle the frontier on the tail ops and persist the
                # checkpoint before analysis resumes from it.
                try:
                    mon.finalize()
                except Exception:  # noqa: BLE001
                    logger.exception("monitor finalize; cold analyze")
            results = analyze(test, history)
            test["results"] = results
            store.save_2(test, results)
            _log_results(results)
            return test
        finally:
            if mon is not None:
                mon.close()
            if has_cluster:
                # Failed OS/DB setup never reaches the in-run snarf site;
                # those logs matter most for diagnosis, so snarf here too
                # (idempotent via the _logs_snarfed flag).
                _snarf_logs_safe(test)
                control.teardown_sessions(test)
            _close_resources(test)
    finally:
        store.stop_logging(log_handler)


def _close_resources(test) -> None:
    """Close test-scoped resources (with-resources parity, core.clj:70):
    anything a suite put in test["resources"] — e.g. the localkv proxy
    router's listener sockets/threads — is closed when the run ends,
    best-effort, never masking the run's own outcome."""
    for r in test.get("resources") or []:
        try:
            r.close()
        except Exception:  # noqa: BLE001
            logger.exception("closing test resource %r", r)


def _setup_os(test) -> None:
    osys = test.get("os")
    if osys is None or not test.get("nodes"):
        return
    logger.info("Setting up OS")
    control.on_nodes(test, osys.setup, phase="setup")


def _setup_db(test) -> None:
    database = test.get("db")
    if database is None or not test.get("nodes"):
        return
    logger.info("Setting up DB")

    def cyc(t, node):
        jdb.cycle_(database, t, node)

    control.on_nodes(test, cyc, phase="setup")
    if isinstance(database, jdb.Primary) and test["nodes"]:
        database.setup_primary(test, test["nodes"][0])


def _teardown_db(test, final: bool = False) -> None:
    database = test.get("db")
    if database is None or not test.get("nodes"):
        return
    if test.get("leave_db_running"):
        logger.info("Leaving DB running for inspection")
        return
    logger.info("Tearing down DB")
    control.on_nodes(test, database.teardown, phase="teardown")


def _run_case(test) -> History:
    """Set up nemesis+clients, run the generator, tear down
    (core.clj:176-214 run-case!)."""
    nem = test.get("nemesis") or jnemesis.NoopNemesis()
    test["nemesis"] = nem.setup(test)
    try:
        # Open one client per node and run its setup! (schema creation
        # etc.) before any worker dispatch, as in core.clj:176-207.
        client_proto = test.get("client")
        if client_proto is not None:
            for node in (test.get("nodes") or [None]):
                c = client_proto.open(test, node)
                try:
                    c.setup(test)
                finally:
                    try:
                        c.close(test)
                    except Exception:  # noqa: BLE001
                        logger.exception("client close after setup")
        logger.info("Running workload")
        return interpreter.run(test)
    finally:
        try:
            test["nemesis"].teardown(test)
        except Exception:  # noqa: BLE001
            logger.exception("nemesis teardown")
        finally:
            # The run-level heal guarantee (nemesis/registry.py): even when
            # the generator phase raised, or the nemesis crashed mid-fault
            # before its own teardown could know about the fault, every
            # registered-but-unresolved undo runs here — no run exits with
            # the cluster still partitioned / skewed / SIGSTOPped.
            _heal_outstanding_faults(test)


def _heal_outstanding_faults(test) -> None:
    reg = test.get("fault_registry")
    if reg is None:
        return
    pending = reg.outstanding()
    if not pending:
        return
    logger.warning("healing %d outstanding fault(s) at teardown: %s",
                   len(pending), ", ".join(pending))
    outcomes = reg.heal_all()
    test["healed_faults"] = {**test.get("healed_faults", {}), **outcomes}
    for key, outcome in outcomes.items():
        if outcome != "healed":
            logger.error("fault %s: %s", key, outcome)


def analyze(test, history: History,
            service: Optional[Any] = None) -> Dict[str, Any]:
    """Run the checker over the history (core.clj:216-232 analyze!).

    ``test["checker"]`` may be a Checker instance or any registry spec
    (a name like "elle-list-append", a ``{"name": ..., **opts}`` dict, a
    mapping, or a list — see checker.core.resolve_checker): workload
    configs can name their analysis declaratively.

    With a ``service`` (the argument, or ``test["service"]`` — a
    serve.CheckService), device-tier checkers route through the shared
    batched checking service instead of running a cold one-shot: N
    concurrent runs share one device and one compiled-engine cache.
    Checkers the service cannot batch fall back to the direct path, and
    a service-side crash degrades to the direct path too — routing is an
    optimization, never a verdict risk.

    Each call reports its wall to ``obs.hist.first_use_stats()``, the
    process's first apart from the later ones: what a first check costs
    over a steady one."""
    t0 = mono_now()
    try:
        with span("entry.analyze", entries=len(history)):
            return _analyze(test, history, service)
    finally:
        observe_analyze(mono_now() - t0)


def _analyze(test, history: History,
             service: Optional[Any]) -> Dict[str, Any]:
    logger.info("Analyzing history (%d ops)", len(history))
    checker = test.get("checker")
    if checker is None:
        return {"valid": True, "note": "no checker configured"}
    if not isinstance(checker, Checker):
        from jepsen_tpu.checker.core import resolve_checker
        checker = resolve_checker(checker)
    opts = {"store_dir": test.get("store_dir")}
    mon = test.get("_monitor")
    if mon is not None:
        # A monitored run resumes the authoritative check from the last
        # monitor epoch (monitor/resume.py): None = soundness doubt, run
        # the cold path below.  A resume crash is likewise just a cold
        # analyze — resumption is an optimization, never a verdict risk.
        from jepsen_tpu.monitor import resume as _mon_resume
        try:
            resumed = _mon_resume.resume_final_check(test, checker, history,
                                                     mon, opts)
        except Exception:  # noqa: BLE001
            logger.exception("monitor resume failed; cold analyze")
            resumed = None
        if resumed is not None:
            logger.info("analysis resumed from monitor epoch %s "
                        "(%s tail op(s) re-checked)",
                        resumed.get("resumed-from-epoch"),
                        resumed.get("tail-ops"))
            if resumed.get("valid") is False:
                _failure_artifacts(test, history)
            return resumed
    service = service if service is not None else test.get("service")
    if service is not None:
        try:
            routed = service.try_route_analyze(test, checker, history, opts)
        except Exception:  # noqa: BLE001
            logger.exception("service routing failed; using direct path")
            routed = None
        if routed is not None:
            if routed.get("valid") is False:
                _failure_artifacts(test, history)
            return routed
    results = check_safe(checker, test, history, opts)
    if results.get("valid") is False:
        _failure_artifacts(test, history)
    return results


def _failure_artifacts(test, history: History) -> None:
    """A failing run always gets human-inspectable artifacts — timeline and
    perf plots — even when the test composed no Timeline/Perf checker
    (checker.clj:207-211 renders on invalid analyses).  Best-effort; never
    masks the verdict."""
    d = test.get("store_dir")
    if not d:
        return
    import os as _os
    try:
        if not _os.path.exists(_os.path.join(d, "timeline.html")):
            from jepsen_tpu.checker.timeline import Timeline
            Timeline().check(test, history, {"store_dir": d})
        if not _os.path.exists(_os.path.join(d, "latency-raw.png")):
            from jepsen_tpu.checker.perf import Perf
            Perf().check(test, history, {"store_dir": d})
    except Exception:  # noqa: BLE001
        logger.exception("failure-artifact rendering")


def _snarf_logs_safe(test) -> None:
    """Snarf at most once per run, never raising (shutdown-hook spirit of
    core.clj:143-163: log download must not mask the real failure)."""
    if test.get("_logs_snarfed"):
        return
    try:
        _snarf_logs(test)
        test["_logs_snarfed"] = True
    except Exception:  # noqa: BLE001
        logger.exception("downloading node logs")


def _snarf_logs(test) -> None:
    """Download db log files into the store dir (core.clj:102-129)."""
    database = test.get("db")
    if not isinstance(database, jdb.LogFiles):
        return
    import os as _os

    def snarf(t, node):
        s = control.session(t, node)
        dest = _os.path.join(t["store_dir"], node)
        _os.makedirs(dest, exist_ok=True)
        for path in database.log_files(t, node):
            try:
                s.download(path, dest)
            except Exception:  # noqa: BLE001
                logger.warning("couldn't download %s from %s", path, node)

    control.on_nodes(test, snarf)


def _log_results(results: Dict[str, Any]) -> None:
    v = results.get("valid")
    if v is True:
        logger.info("Everything looks good! (⌐■_■)")
    elif v == UNKNOWN:
        logger.warning("Errors occurred during analysis; verdict unknown")
        for where, tb in iter_analysis_errors(results):
            logger.warning("analysis error in %s:\n%s", "/".join(where), tb)
    else:
        logger.error("Analysis invalid! (ﾉಥ益ಥ）ﾉ ┻━┻")


def iter_analysis_errors(results: Any, path=()):
    """Yield ``(path, reason)`` for every unknown-with-a-reason anywhere in
    a (possibly nested — compose / independent) result map: crashed
    checkers contribute their traceback, non-crash unknowns (capacity
    ceilings, never-succeeded ops, cancellations) their ``error`` string."""
    if not isinstance(results, dict):
        return
    if results.get("valid") == UNKNOWN:
        if "traceback" in results:
            yield path, results["traceback"]
        elif "error" in results:
            yield path, str(results["error"])
        elif results.get("cancelled"):
            yield path, "cancelled (competition loser)"
    for k, value in results.items():
        if isinstance(value, dict):
            yield from iter_analysis_errors(value, path + (str(k),))


def run_tests(tests, raise_on_failure: bool = False, workers: int = 1,
              service: Optional[Any] = None):
    """Run a sequence of tests, collecting verdicts (cli.clj:433-519
    test-all).

    ``service`` (a serve.CheckService) is injected into every test map so
    each run's analysis phase routes through one shared batched checking
    service; with ``workers > 1`` the campaign's runs execute
    concurrently and their checks batch onto the device together —
    N concurrent runs, one device.  Results keep the input order."""
    tests = list(tests)
    if service is not None:
        for t in tests:
            t.setdefault("service", service)

    def one(t):
        try:
            done = run(t)
            return {"name": done.get("name"),
                    "dir": done.get("store_dir"),
                    "valid": done.get("results", {}).get("valid")}
        except Exception as e:  # noqa: BLE001
            logger.error("test crashed: %s", e)
            return {"name": t.get("name"), "valid": UNKNOWN,
                    "error": traceback.format_exc()}

    if workers > 1 and len(tests) > 1:
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(max_workers=workers,
                                thread_name_prefix="campaign") as ex:
            results = list(ex.map(one, tests))
    else:
        results = [one(t) for t in tests]
    n_bad = sum(1 for r in results if r["valid"] is False)
    n_unknown = sum(1 for r in results if r["valid"] == UNKNOWN)
    summary = {"results": results, "failures": n_bad, "unknown": n_unknown,
               "exit": 2 if n_unknown and not n_bad else (1 if n_bad else 0)}
    if raise_on_failure and summary["exit"]:
        raise RuntimeError(f"{n_bad} failures, {n_unknown} unknown")
    return summary
