"""Command-line runner for test suites.

Parity: jepsen.cli (jepsen/src/jepsen/cli.clj): a shared option vocabulary
(nodes, ssh, concurrency with the "3n" syntax, time limits, repeat counts —
cli.clj:64-168), a ``test`` subcommand built from a suite's test function
(single-test-cmd, cli.clj:355), ``test-all`` sweeps (cli.clj:491), an
``analyze`` mode for re-checking stored histories (the store/REPL pattern),
and ``serve`` for the results browser.  Beyond the reference: ``submit``
POSTs a stored history to a running serve, and ``trace`` fetches a
request's merged distributed trace (optionally exporting Chrome
trace-event JSON for ui.perfetto.dev).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Callable, Dict, List, Optional, Sequence

from jepsen_tpu import core, store


def add_test_opts(p: argparse.ArgumentParser) -> None:
    """Shared test options (cli.clj:64-111 test-opt-spec)."""
    p.add_argument("--node", "-n", action="append", dest="nodes",
                   help="node hostname (repeatable)")
    p.add_argument("--nodes", dest="nodes_csv",
                   help="comma-separated node list")
    p.add_argument("--nodes-file", help="file with one node per line")
    p.add_argument("--username", default="root")
    p.add_argument("--password")
    p.add_argument("--ssh-private-key")
    p.add_argument("--ssh-port", type=int, default=22)
    p.add_argument("--dummy-ssh", action="store_true",
                   help="no-op control plane (in-process testing)")
    p.add_argument("--dummy-ssh-record", action="store_true",
                   help="record-only control plane: log commands, execute "
                        "nothing (smoke-tests suite control logic)")
    p.add_argument("--no-ssh", action="store_true",
                   help="never open SSH connections (cli.clj:85); "
                        "control commands are recorded, not executed")
    p.add_argument("--strict-host-key-checking", action="store_true",
                   help="verify SSH host keys (cli.clj:82; default off, "
                        "like the reference's default)")
    p.add_argument("--concurrency", "-c", default="1n",
                   help="worker count; '3n' = 3x node count")
    p.add_argument("--time-limit", type=float, default=60.0,
                   help="workload duration in seconds")
    p.add_argument("--test-count", type=int, default=1,
                   help="how many times to run the test")
    p.add_argument("--leave-db-running", action="store_true")
    p.add_argument("--logging-json", action="store_true",
                   help="jepsen.log as JSON lines (cli.clj:98)")
    p.add_argument("--store", default="store", help="results directory")
    p.add_argument("--monitor", action="store_true",
                   help="check the run online: stream ops into the "
                        "checker during the run, refute early, resume "
                        "the final check from monitor state")
    p.add_argument("--monitor-epoch", type=int, default=None,
                   help="monitor epoch size in ops (default 256)")
    p.add_argument("--monitor-abort", action="store_true",
                   help="cut the generator as soon as the monitor "
                        "confirms a refutation")


def parse_nodes(args) -> List[str]:
    if args.nodes:
        return args.nodes
    if getattr(args, "nodes_csv", None):
        return [n.strip() for n in args.nodes_csv.split(",") if n.strip()]
    if args.nodes_file:
        with open(args.nodes_file) as f:
            return [l.strip() for l in f if l.strip()]
    return ["n1", "n2", "n3", "n4", "n5"]  # cli.clj:18 default


def test_opts_to_map(args) -> Dict[str, Any]:
    return {
        "nodes": parse_nodes(args),
        "ssh": {"username": args.username,
                "password": args.password,
                "private_key_path": args.ssh_private_key,
                "port": args.ssh_port,
                "strict_host_key_checking":
                    getattr(args, "strict_host_key_checking", False),
                "dummy": "record"
                if (getattr(args, "dummy_ssh_record", False)
                    or getattr(args, "no_ssh", False))
                else args.dummy_ssh},
        "concurrency": args.concurrency,
        "time_limit": args.time_limit,
        "leave_db_running": args.leave_db_running,
        "logging_json": getattr(args, "logging_json", False),
        "store_base": args.store,
        "monitor": getattr(args, "monitor", False),
        "monitor_epoch": getattr(args, "monitor_epoch", None),
        "monitor_abort": getattr(args, "monitor_abort", False),
    }


def single_test_cmd(test_fn: Callable[[Dict[str, Any]], Dict[str, Any]],
                    opt_fn: Optional[Callable] = None,
                    argv: Optional[Sequence[str]] = None,
                    prog: str = "jepsen-tpu") -> int:
    """Build and run the standard CLI around a suite's test constructor
    (cli.clj:355 single-test-cmd).  ``opt_fn`` may add suite options."""
    parser = argparse.ArgumentParser(prog=prog)
    sub = parser.add_subparsers(dest="cmd", required=True)

    pt = sub.add_parser("test", help="run one test")
    add_test_opts(pt)
    if opt_fn:
        opt_fn(pt)

    pa = sub.add_parser("analyze", help="re-check a stored run")
    pa.add_argument("dir", help="store run directory (or .../latest)")

    ps = sub.add_parser("serve",
                        help="results web browser + checking service")
    ps.add_argument("--port", type=int, default=8080)
    ps.add_argument("--store", default="store")
    ps.add_argument("--no-service", action="store_true",
                    help="results browser only, no checking service")
    ps.add_argument("--max-lanes", type=int, default=64,
                    help="lanes per device dispatch")
    ps.add_argument("--max-queue", type=int, default=4096,
                    help="admission-control queue depth (cells)")
    ps.add_argument("--workers", type=int, default=3,
                    help="checking-service worker replicas (the fault-"
                         "tolerant fleet; 1 = a single CheckService)")
    ps.add_argument("--journal-dir", default=None,
                    help="fleet in-flight journal directory (default "
                         "<store>/fleet-journal); 'none' disables "
                         "crash journaling")
    ps.add_argument("--procs", action="store_true",
                    help="run fleet workers as real OS processes behind "
                         "the wire protocol (serve/transport.py), each "
                         "dialed through a chaos-controllable net_proxy "
                         "link; implies the fleet path even with "
                         "--workers 1")
    ps.add_argument("--telemetry-s", type=float, default=None,
                    help="worker telemetry push interval in seconds "
                         "(default JEPSEN_TPU_TELEMETRY_S or 1.0; <= 0 "
                         "disables the push plane)")
    ps.add_argument("--recorder", action="store_true",
                    help="arm the flight recorder at startup (fleet-wide "
                         "with --procs); also togglable at runtime via "
                         "POST /recorder?on=1")

    pf = sub.add_parser("fleet",
                        help="run a fleetport: the multi-host control "
                             "plane workers register with "
                             "(serve/fleetport.py)")
    pf.add_argument("--listen", default="0.0.0.0:7600",
                    metavar="HOST:PORT",
                    help="address the REGISTER/renewal listener binds "
                         "(default 0.0.0.0:7600)")
    pf.add_argument("--port", type=int, default=8080,
                    help="web port (GET /fleet, /metrics, /healthz)")
    pf.add_argument("--store", default="store")
    pf.add_argument("--lease-s", type=float, default=None,
                    help="worker lease duration in seconds (default "
                         "JEPSEN_TPU_LEASE_S or 10)")
    pf.add_argument("--max-lanes", type=int, default=64)
    pf.add_argument("--max-queue", type=int, default=4096)
    pf.add_argument("--journal-dir", default=None,
                    help="in-flight journal directory (default "
                         "<store>/fleet-journal); 'none' disables")
    pf.add_argument("--telemetry-s", type=float, default=None)

    pq = sub.add_parser("submit",
                        help="submit a stored history to a running serve")
    pq.add_argument("dir", help="store run directory (or .../latest)")
    pq.add_argument("--url", default="http://127.0.0.1:8080",
                    help="base URL of the running serve")
    pq.add_argument("--kind", choices=["wgl", "elle"], default="wgl")
    pq.add_argument("--model", default="cas-register",
                    help="device model name (wgl kind)")
    pq.add_argument("--workload", default="list-append",
                    help="elle workload (elle kind)")
    pq.add_argument("--realtime", action="store_true")
    pq.add_argument("--independent", action="store_true",
                    help="history is an independent workload: restore "
                         "[k, v] values to keyed tuples so the service "
                         "splits per key")
    pq.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline in seconds")
    pq.add_argument("--tenant", default=None,
                    help="attribute the request to this tenant (quota, "
                         "priority, per-tenant SLO cut)")
    pq.add_argument("--tenant-token", default=None,
                    help="tenant auth token, sent as X-Tenant-Token; "
                         "defaults to JEPSEN_TPU_TENANT_TOKEN from the "
                         "environment (prefer the env — argv leaks into "
                         "process listings)")

    ptr = sub.add_parser("trace",
                         help="fetch a request's merged distributed trace "
                              "from a running serve")
    ptr.add_argument("request_id", help="request id (serve.request-id in a "
                                        "verdict, or X-Request-Id)")
    ptr.add_argument("--url", default="http://127.0.0.1:8080",
                     help="base URL of the running serve")
    ptr.add_argument("--perfetto", metavar="PATH", default=None,
                     help="also write the trace as Chrome trace-event JSON "
                          "to PATH (load it at ui.perfetto.dev)")

    args = parser.parse_args(argv)

    if args.cmd == "test":
        from jepsen_tpu.ops.cache import init_compilation_cache
        init_compilation_cache()
        opts = test_opts_to_map(args)
        for k, v in vars(args).items():
            if k not in opts and v is not None:
                opts[k.replace("-", "_")] = v
        failures = 0
        for i in range(args.test_count):
            test = test_fn(dict(opts))
            done = core.run(test)
            valid = done.get("results", {}).get("valid")
            print(json.dumps({"run": i, "dir": done.get("store_dir"),
                              "valid": valid}))
            if valid is not True:
                failures += 1
        return 1 if failures else 0

    if args.cmd == "analyze":
        test = store.load_test(args.dir)
        history = store.load_history(args.dir)
        full = test_fn(test)  # rebuild checker from suite
        results = core.analyze(full, history)
        print(json.dumps(results, indent=2, default=str))
        return 0 if results.get("valid") is True else 1

    if args.cmd == "serve":
        from jepsen_tpu.web import serve
        service = None
        if not args.no_service:
            # The fleet is the default serving path: N worker services
            # behind the fault-tolerant router (serve/fleet.py).
            # --workers 1 keeps the old single-service behaviour.
            if max(1, args.workers) > 1 or args.procs:
                from jepsen_tpu.serve.fleet import Fleet, ProcFleet
                jdir = args.journal_dir
                if jdir is None:
                    jdir = os.path.join(args.store, "fleet-journal")
                elif jdir == "none":
                    jdir = None
                fleet_cls = ProcFleet if args.procs else Fleet
                service = fleet_cls(workers=args.workers,
                                    journal_dir=jdir,
                                    max_lanes=args.max_lanes,
                                    max_queue_cells=args.max_queue,
                                    telemetry_s=args.telemetry_s)
            else:
                from jepsen_tpu.serve import CheckService
                service = CheckService(max_lanes=args.max_lanes,
                                       max_queue_cells=args.max_queue)
        if args.recorder:
            setter = getattr(service, "set_recorder", None)
            if setter is not None:
                setter(True)
            else:
                from jepsen_tpu.obs.recorder import RECORDER
                RECORDER.enable()
        # SIGTERM must reach the finally below: with --procs the workers
        # are setsid'd OS processes — dying without service.close() would
        # orphan them (SIGINT already raises KeyboardInterrupt).
        import signal

        def _term(signum, frame):  # noqa: ARG001 — signal signature
            raise SystemExit(143)

        try:
            signal.signal(signal.SIGTERM, _term)
        except ValueError:  # not the main thread (library-embedded call)
            pass
        try:
            serve(base=args.store, port=args.port, service=service)
        finally:
            if service is not None:
                service.close(timeout=30.0)
        return 0

    if args.cmd == "fleet":
        from jepsen_tpu.serve.fleetport import Fleetport
        from jepsen_tpu.web import serve
        lhost, _, lport = args.listen.rpartition(":")
        jdir = args.journal_dir
        if jdir is None:
            jdir = os.path.join(args.store, "fleet-journal")
        elif jdir == "none":
            jdir = None
        service = Fleetport(listen_host=lhost or "0.0.0.0",
                            listen_port=int(lport),
                            lease_s=args.lease_s,
                            journal_dir=jdir,
                            max_lanes=args.max_lanes,
                            max_queue_cells=args.max_queue,
                            telemetry_s=args.telemetry_s)
        print(json.dumps({
            "fleetport": {"host": service.listen_host,
                          "port": service.listen_port},
            "lease-s": service.registry.lease_s,
            # boolean only — the token itself is never printed
            "auth-enabled": bool(service._token)}), flush=True)
        import signal as _signal

        def _fterm(signum, frame):  # noqa: ARG001 — signal signature
            raise SystemExit(143)

        try:
            _signal.signal(_signal.SIGTERM, _fterm)
        except ValueError:  # not the main thread
            pass
        try:
            serve(base=args.store, port=args.port, service=service)
        finally:
            service.close(timeout=30.0)
        return 0

    if args.cmd == "submit":
        return submit_cmd(args)

    if args.cmd == "trace":
        return trace_cmd(args)

    return 2


def submit_cmd(args) -> int:
    """POST a stored run's history to a running serve's /submit endpoint
    and print the verdict JSON."""
    import urllib.request
    history = store.load_history(args.dir)
    body = {"ops": [op.to_dict() for op in history],
            "kind": args.kind, "realtime": args.realtime,
            "independent": args.independent}
    if args.kind == "wgl":
        body["model"] = args.model
    else:
        body["workload"] = args.workload
    if args.deadline is not None:
        body["deadline_s"] = args.deadline
    headers = {"Content-Type": "application/json"}
    if args.tenant is not None:
        body["tenant"] = args.tenant
        token = args.tenant_token \
            or os.environ.get("JEPSEN_TPU_TENANT_TOKEN", "")
        if token:
            headers["X-Tenant-Token"] = token
    req = urllib.request.Request(
        args.url.rstrip("/") + "/submit",
        data=json.dumps(body).encode(),
        headers=headers, method="POST")
    with urllib.request.urlopen(req) as resp:
        results = json.loads(resp.read())
    print(json.dumps(results, indent=2, default=str))
    return 0 if results.get("valid") is True else 1


def trace_cmd(args) -> int:
    """GET /trace/<request-id> from a running serve and print the merged
    causal tree; ``--perfetto PATH`` additionally exports it as Chrome
    trace-event JSON for ui.perfetto.dev."""
    import urllib.error
    import urllib.request
    url = f"{args.url.rstrip('/')}/trace/{args.request_id}"
    try:
        with urllib.request.urlopen(url) as resp:
            trace = json.loads(resp.read())
    except urllib.error.HTTPError as e:
        print(json.dumps({"error": f"HTTP {e.code}: {e.read().decode()}"}),
              file=sys.stderr)
        return 1
    print(json.dumps(trace, indent=2, default=str))
    if args.perfetto:
        from jepsen_tpu.obs.trace import chrome_events_from_trace, write_chrome
        write_chrome(args.perfetto, chrome_events_from_trace(trace))
        print(f"perfetto export: {args.perfetto}", file=sys.stderr)
    return 0


def test_all_cmd(tests_fn: Callable[[Dict[str, Any]], List[Dict[str, Any]]],
                 opt_fn: Optional[Callable] = None,
                 argv: Optional[Sequence[str]] = None) -> int:
    """Run a suite's whole sweep matrix (cli.clj:433-519).

    The whole campaign shares one checking service: every test's analyze
    phase routes through a single CheckService, so the sweep's histories
    are continuously batched onto the device engines and compiled shapes
    are reused across tests.  ``--campaign-workers N`` overlaps N runs
    (their checks coalesce into shared dispatches); ``--no-service``
    restores the per-test direct checker path."""
    parser = argparse.ArgumentParser()
    add_test_opts(parser)
    parser.add_argument("--campaign-workers", type=int, default=1,
                        help="concurrent test runs in the sweep")
    parser.add_argument("--no-service", action="store_true",
                        help="check each test directly, no shared service")
    if opt_fn:
        opt_fn(parser)
    args = parser.parse_args(argv)
    opts = test_opts_to_map(args)
    service = None
    if not args.no_service:
        from jepsen_tpu.serve import CheckService
        service = CheckService()
    try:
        summary = core.run_tests(tests_fn(dict(opts)),
                                 workers=max(1, args.campaign_workers),
                                 service=service)
    finally:
        if service is not None:
            service.close(timeout=60.0)
    for r in summary["results"]:
        print(json.dumps(r, default=str))
    print(json.dumps({"failures": summary["failures"],
                      "unknown": summary["unknown"]}))
    return summary["exit"]


def _main() -> int:
    """`python -m jepsen_tpu.cli` — suite-less entry point: analyze a
    stored run (stats-only: the persisted test map carries no checker
    objects) or serve the results browser (cli.clj:521's -main).
    Running a *test* needs a suite module's test function — refuse it
    rather than report an empty workload as valid."""
    def test_fn(opts: Dict[str, Any]) -> Dict[str, Any]:
        if "checker" not in opts:
            from jepsen_tpu.checker import Stats
            opts = {**opts, "checker": Stats()}
        return opts

    if sys.argv[1:2] == ["test"]:
        print("jepsen-tpu: `test` needs a suite runner "
              "(python -m suites.<name>.runner test ...); the bare module "
              "only supports analyze/serve", file=sys.stderr)
        return 2
    return single_test_cmd(test_fn, prog="jepsen-tpu")


if __name__ == "__main__":
    sys.exit(_main())
