#!/usr/bin/env python
"""Observability smoke: one merged trace per request, under real chaos.

Runs a 48-history mixed campaign (wgl cas-register + elle list-append,
a third corrupted) through a 3-worker ProcFleet — real OS worker
processes behind the serve/transport.py wire — while the nemesis severs
one worker's proxy link (partition + heal) and SIGKILLs another worker's
process mid-campaign (supervisor respawn).  Then asserts the telescope
actually resolved what happened:

- every completed request has a MERGED trace (fleet.merged_trace): one
  causal tree whose every absorbed remote span parents to a span in the
  tree — no orphan subtrees, even for requests that rerouted or hedged
  across the partition/kill;
- at least one trace carries spans from >= 2 distinct pids (the fleet
  process and a worker process): the wire context propagation is real,
  not an in-process shortcut;
- the Perfetto export (obs.trace.write_chrome) validates as Chrome
  trace-event JSON — a dict with a non-empty ``traceEvents`` list of
  "X"/"i" events, each with name/ph/ts/pid — loadable at
  ui.perfetto.dev;
- the fleet-wide /metrics scrape merged per-worker histograms and lists
  one entry per worker;
- the flight recorder's toll is bounded: the same warmed CheckService
  campaign recorder-off vs recorder-on stays within a generous CI noise
  band (a tight budget needs quiet hardware, not a shared CI runner:
  not measured, PERF.md section 7).

Writes the full report to argv[1] (default /tmp/obs_smoke_report.json)
and the Perfetto trace to argv[2] (default /tmp/obs_smoke_trace.json) —
CI uploads both as artifacts.
"""

import json
import os
import shutil
import signal
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Arm the flight recorder before any jepsen_tpu import constructs the
# process singleton — and before the fleet spawns worker processes, so
# they inherit the knob and record their own rings too.
os.environ["JEPSEN_TPU_FLIGHT_RECORDER"] = "1"

from jepsen_tpu.nemesis.registry import FaultRegistry  # noqa: E402
from jepsen_tpu.obs.recorder import RECORDER
from jepsen_tpu.obs.trace import chrome_events_from_trace, write_chrome
from jepsen_tpu.serve import CheckService
from jepsen_tpu.serve.chaos import ChaosNemesis
from jepsen_tpu.serve.fleet import ProcFleet
from jepsen_tpu.synth import (
    cas_register_history, corrupt_list_append, corrupt_reads,
    list_append_history,
)

N_WGL, N_ELLE, CLIENTS = 36, 12, 4
DEADLINE_S = 60.0
# CI noise band for the recorder toll; what tracing costs on the chip is
# in PERF.md (PR 25).
TOLL_BAND = 0.25


def build_workload():
    jobs = []
    for s in range(N_WGL):
        h = cas_register_history(60, concurrency=4, seed=s)
        if s % 3 == 2:
            h = corrupt_reads(h, n=1, seed=s)
        jobs.append(("wgl", h))
    for s in range(N_ELLE):
        h = list_append_history(25, seed=1000 + s)
        if s % 3 == 2:
            h = corrupt_list_append(h, anomaly_p=0.5, seed=s)
        jobs.append(("elle", h))
    return jobs


def submit_kw(kind):
    return ({"model": "cas-register"} if kind == "wgl"
            else {"workload": "list-append"})


def run_fleet(fleet, jobs, deadline_s=DEADLINE_S):
    reqs_out = [None] * len(jobs)

    def client(span):
        reqs = []
        for i in span:
            kind, h = jobs[i]
            reqs.append((i, fleet.submit(h, kind=kind,
                                         deadline_s=deadline_s,
                                         **submit_kw(kind))))
        for i, r in reqs:
            r.wait(timeout=180)
            reqs_out[i] = r

    threads = [threading.Thread(target=client,
                                args=(range(j, len(jobs), CLIENTS),))
               for j in range(CLIENTS)]
    for t in threads:
        t.start()
    return threads, reqs_out


def audit_trace(trace):
    """Connectivity audit of one merged trace: returns (orphans, pids).
    An orphan is an absorbed remote payload whose parent-span-id names
    no span in the tree — a subtree the merge failed to attach."""
    ids = {trace.get("span-id")}
    for r in trace.get("remote", []):
        ids.add(r.get("span-id"))
    orphans = [{"request-id": r.get("request-id"),
                "span-id": r.get("span-id"),
                "parent-span-id": r.get("parent-span-id")}
               for r in trace.get("remote", [])
               if r.get("parent-span-id") not in ids]
    pids = {trace.get("pid")} | {r.get("pid")
                                 for r in trace.get("remote", [])}
    return orphans, {p for p in pids if p is not None}


def validate_chrome(doc):
    """The export must be loadable Chrome trace-event JSON."""
    assert isinstance(doc, dict), "chrome doc must be a JSON object"
    events = doc.get("traceEvents")
    assert isinstance(events, list) and events, "traceEvents empty"
    for ev in events:
        assert ev.get("ph") in ("X", "i"), f"bad phase: {ev}"
        for k in ("name", "ts", "pid"):
            assert k in ev, f"event missing {k!r}: {ev}"
        if ev["ph"] == "X":
            assert ev.get("dur", 0) > 0, f"X event without dur: {ev}"
    json.loads(json.dumps(doc))  # round-trips as plain JSON


def phase_traces(jobs, journal_dir):
    """The campaign under chaos, then the trace audit."""
    fleet = ProcFleet(workers=3, spawn=True, journal_dir=journal_dir,
                      max_lanes=48, hedge_s=0.3,
                      default_deadline_s=DEADLINE_S,
                      supervise_s=0.25)
    chaos = ChaosNemesis(fleet, registry=FaultRegistry(), seed=7)
    # Warm pass: each worker process compiles its own engines.
    warm, _ = run_fleet(fleet, jobs[:3] + jobs[-3:])
    for t in warm:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in warm), "warm pass hung"

    threads, reqs = run_fleet(fleet, jobs)
    time.sleep(0.3)                       # let the campaign start flowing
    part = chaos.partition_worker(0)      # RST + ECONNREFUSED
    victim_pid = fleet.workers[2].service.launcher.proc.pid
    os.kill(victim_pid, signal.SIGKILL)   # supervisor must respawn it
    time.sleep(1.0)
    chaos.heal(part)

    for t in threads:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in threads), "fleet clients hung"
    leftover = chaos.heal_all()
    assert not leftover, f"faults survived heal: {leftover}"

    audits = []
    best = None                           # the trace with the most pids
    for req in reqs:
        trace = fleet.merged_trace(req.id)
        assert trace is not None, f"request {req.id}: no merged trace"
        assert trace.get("parent-span-id") is None, (
            f"request {req.id}: root span has a parent")
        assert trace.get("spans"), f"request {req.id}: root has no spans"
        foreign = [r for r in trace.get("remote", [])
                   if r.get("trace-id") != trace.get("trace-id")]
        assert not foreign, (
            f"request {req.id}: absorbed spans from a foreign trace: "
            f"{foreign}")
        orphans, pids = audit_trace(trace)
        assert not orphans, (
            f"request {req.id}: orphan spans in merged trace: {orphans}")
        audits.append({"request-id": trace["request-id"],
                       "trace-id": trace["trace-id"],
                       "n_remote": len(trace.get("remote", [])),
                       "pids": sorted(pids)})
        if best is None or len(pids) > len(audit_trace(best)[1]):
            best = trace

    multi_pid = [a for a in audits if len(a["pids"]) >= 2]
    assert multi_pid, (
        "no trace carries spans from >= 2 pids — wire propagation is "
        "not reaching the worker processes")

    snap = fleet.metrics.snapshot()
    fleet.close(timeout=60.0)

    assert len(snap.get("workers", [])) == 3, "scrape missed workers"
    assert any(k.startswith("edge:") for k in snap.get("histograms", {})), (
        "fleet-wide histogram merge produced no lifecycle edges")
    return audits, best, snap


def phase_toll(jobs):
    """Recorder-off vs recorder-on wall on a warmed in-process service."""
    wgl = [(k, h) for k, h in jobs if k == "wgl"][:16]
    svc = CheckService(max_lanes=32, capacity=64)

    def run():
        t0 = time.monotonic()
        reqs = [svc.submit(h, kind=kind, deadline_s=120.0,
                           **submit_kw(kind)) for kind, h in wgl]
        for r in reqs:
            r.wait(timeout=300)
        return time.monotonic() - t0

    run()                                 # warm the bucket ladder
    RECORDER.disable()
    t_off = min(run() for _ in range(2))
    RECORDER.enable()
    t_on = min(run() for _ in range(2))
    svc.close(timeout=30.0)
    overhead = t_on / t_off - 1.0 if t_off else 0.0
    assert overhead < TOLL_BAND, (
        f"recorder toll {overhead:.1%} beyond the {TOLL_BAND:.0%} CI "
        f"noise band — the off path is not free")
    return {"recorder_off_s": round(t_off, 3),
            "recorder_on_s": round(t_on, 3),
            "overhead": round(overhead, 4)}


def main():
    report_path = (sys.argv[1] if len(sys.argv) > 1
                   else "/tmp/obs_smoke_report.json")
    trace_path = (sys.argv[2] if len(sys.argv) > 2
                  else "/tmp/obs_smoke_trace.json")
    jobs = build_workload()
    tmp = tempfile.mkdtemp(prefix="obs-smoke-")
    try:
        audits, best, snap = phase_traces(jobs,
                                          os.path.join(tmp, "journal"))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    # Perfetto export: the multi-pid trace plus this process's flight
    # recorder (chaos injections, reroutes/hedges) on the same timeline.
    events = chrome_events_from_trace(best) + RECORDER.chrome_events()
    write_chrome(trace_path, events)
    with open(trace_path) as f:
        validate_chrome(json.load(f))

    toll = phase_toll(jobs)

    report = {"traces": audits,
              "multi_pid_traces": len([a for a in audits
                                       if len(a["pids"]) >= 2]),
              "exported_trace": {"request-id": best["request-id"],
                                 "pids": sorted(audit_trace(best)[1]),
                                 "path": trace_path},
              "recorder_toll": toll,
              "recorder": RECORDER.stats(),
              "fleet_metrics": snap}
    with open(report_path, "w") as f:
        json.dump(report, f, indent=2, default=str)
    print(json.dumps({
        "traces_audited": len(audits),
        "multi_pid_traces": report["multi_pid_traces"],
        "recorder_overhead": toll["overhead"],
        "events_recorded": report["recorder"]["recorded"],
    }))
    print(f"obs smoke OK: {len(audits)} merged traces fully connected "
          f"under partition+kill, {report['multi_pid_traces']} spanning "
          f">=2 pids, perfetto export valid at {trace_path}, recorder "
          f"toll {toll['overhead']:.1%} within band; report at "
          f"{report_path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
