#!/usr/bin/env python
"""chip_smoke.py: today's checker, once, on one real TPU chip.

The quickest proof that the main path still starts on hardware.  One
process holds the chip for its whole life; it sets neither a platform
nor a cache directory, starts no child process, reads nothing from
``store/`` and needs no network.  It fails at once when JAX finds no
TPU, and any phase's failure ends the run non-zero with its traceback.

Phases, each through the entry points a user calls, each held to the
host oracles (``wgl_cpu``, host ``elle``) for verdict parity:

  0  device   ``jax.devices()[0].platform == "tpu"``, versions
  1  offline  one 10k-op CAS-register history through ``core.analyze``
              with the default ``linearizable`` checker: clean, then
              with two corrupted reads (witness + refuting op)
  2  hard     48 doomed CAS ops + 10k ops: window >= 64, two ghost
              words, the capacity ladder
  3  keyed    512 keys x 200 ops through ``independent.checker``: the
              batched engine and its donated carries
  4  served   one ``CheckService``: 256 concurrent 200-op requests from
              4 threads (megabatch), one 10k-op request, 32 elle
              list-append requests
  5  report   compile-cache directory non-empty; one JSON report line,
              then the device stamp

The histories are the benchmark's shapes (``BENCHMARK.json``: 10k-op
cas-register histories, 200-op crash-bearing keyed lanes) from ``synth``'s
generators; ``--seed`` shifts every generator seed.  Each phase runs its host
oracles on a second thread beside the device work: XLA compiles release
the interpreter lock, so a cold run hides the oracles (about a quarter of
the serial wall) inside them.  Walls are therefore cold observations of
one run, compiles and that contention included, not benchmark numbers.

  python chip_smoke.py [--seed N]

Only when every phase passed: exit 0, the run's observations as one
``{"report": {...}}`` line, and as the last stdout line exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, Iterator, List, Tuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

#: result keys / analyzers that mean a host tier answered for the device
_HOST_ANALYZERS = ("wgl-cpu", "elle-cpu")


def require(cond: bool, msg: str, *ctx: Any) -> None:
    """A phase check that survives ``python -O`` (unlike ``assert``)."""
    if not cond:
        raise RuntimeError(f"chip_smoke: {msg}"
                           + "".join(f"\n  {c!r:.600}" for c in ctx))


def say(msg: str) -> None:
    print(f"[chip_smoke +{time.strftime('%H:%M:%S')}] {msg}", flush=True)


def _walk(res: Any) -> Iterator[Dict[str, Any]]:
    """Every dict nested in a result (per-key and per-cell results ride
    inside the aggregate)."""
    if isinstance(res, dict):
        yield res
        for k, v in res.items():
            if k != "witness":      # the CPU re-derivation is host by design
                yield from _walk(v)
    elif isinstance(res, (list, tuple)):
        for v in res:
            yield from _walk(v)


def require_device_answer(res: Dict[str, Any], what: str) -> None:
    """The verdict is definite and no fallback chain, host solver or host
    analyzer produced any part of it."""
    require(res.get("valid") in (True, False),
            f"{what}: verdict is not definite", res)
    for d in _walk(res):
        require("fallback-chain" not in d and "fallback" not in d,
                f"{what}: a device failure fell back to the host", d)
        require(d.get("solver") not in _HOST_ANALYZERS
                and d.get("analyzer") not in _HOST_ANALYZERS,
                f"{what}: answered by a host tier", d)


def same_refutation(dev: Dict[str, Any], oracle: Dict[str, Any]) -> bool:
    """Device and oracle agree on the verdict and, when refuted, on the
    refuting op."""
    if dev.get("valid") != oracle.get("valid"):
        return False
    if oracle.get("valid") is not False:
        return True
    return isinstance(dev.get("op"), dict) \
        and dev["op"].get("index") == oracle["op"].get("index")


# ---------------------------------------------------------------------------
# Phase 0: device
# ---------------------------------------------------------------------------

def phase_device() -> Dict[str, Any]:
    """Fail at once unless the first JAX device is a TPU."""
    import importlib.metadata as md

    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU: jax.devices()[0] is {d.platform!r} "
            f"({d.device_kind}); this program only runs on the chip")
    stamp = {"platform": d.platform, "kind": d.device_kind,
             "count": len(devs)}
    versions = {"jax": jax.__version__}
    for pkg in ("jaxlib", "libtpu"):
        try:
            versions[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            versions[pkg] = None
    say(f"device {stamp} versions {versions}")
    return {"device": stamp, "versions": versions}


# ---------------------------------------------------------------------------
# Phase 1: offline one-shot, the north-star shape
# ---------------------------------------------------------------------------

def phase_offline(seed: int, n_ops: int = 10_000) -> Dict[str, Any]:
    from jepsen_tpu import core
    from jepsen_tpu.checker import wgl_cpu
    from jepsen_tpu.checker.linearizable import linearizable
    from jepsen_tpu.models import get_model
    from jepsen_tpu.synth import cas_register_history, corrupt_reads

    model = get_model("cas-register")
    test = {"name": "chip-smoke-offline", "checker": linearizable(model)}
    clean = cas_register_history(n_ops, concurrency=8, crash_p=0.0003,
                                 seed=2026 + seed)
    bad = corrupt_reads(clean, n=2, seed=seed, within=0.15)
    cm = model.cpu_model()
    with ThreadPoolExecutor(max_workers=1) as host:
        want_clean = host.submit(wgl_cpu.check, cm, clean)
        want_bad = host.submit(wgl_cpu.check, cm, bad)
        t0 = time.monotonic()
        r = core.analyze(test, clean)
        clean_s = time.monotonic() - t0
        t0 = time.monotonic()
        rb = core.analyze(test, bad)
        bad_s = time.monotonic() - t0
        oracle, oracle_bad = want_clean.result(), want_bad.result()
    require_device_answer(r, "offline clean")
    require(r["valid"] is True and r.get("analyzer") == "wgl-tpu",
            "offline clean: expected valid from wgl-tpu", r)
    require(oracle["valid"] is True, "offline clean: wgl_cpu disagrees",
            oracle)
    require_device_answer(rb, "offline corrupted")
    require(rb["valid"] is False and rb.get("analyzer") == "wgl-tpu"
            and isinstance(rb.get("witness"), dict),
            "offline corrupted: expected a witnessed refutation", rb)
    require(same_refutation(rb, oracle_bad),
            "offline corrupted: refuting op differs from wgl_cpu",
            rb.get("op"), oracle_bad.get("op"))
    return {"ops": n_ops, "clean_s": round(clean_s, 3),
            "refuted_s": round(bad_s, 3),
            "configs_explored": r.get("configs-explored"),
            "max_capacity_reached": r.get("max-capacity-reached"),
            "window": r.get("window")}


# ---------------------------------------------------------------------------
# Phase 2: crash-heavy
# ---------------------------------------------------------------------------

def phase_hard(seed: int, n_ops: int = 10_000,
               n_doomed: int = 48) -> Dict[str, Any]:
    """Linearizable by construction, so the device must say valid with no
    fallback; the host oracle costs minutes here and is not run."""
    from jepsen_tpu import core
    from jepsen_tpu.checker.linearizable import linearizable
    from jepsen_tpu.history import History
    from jepsen_tpu.models import get_model
    from jepsen_tpu.synth import cas_register_history, doomed_cas_padding

    model = get_model("cas-register")
    work = cas_register_history(n_ops, concurrency=8, crash_p=0.0008,
                                seed=11 + seed)
    h = History(doomed_cas_padding(n_doomed) + list(work), reindex=True)
    t0 = time.monotonic()
    r = core.analyze({"name": "chip-smoke-hard",
                      "checker": linearizable(model)}, h)
    wall = time.monotonic() - t0
    require_device_answer(r, "hard")
    require(r["valid"] is True and r.get("analyzer") == "wgl-tpu",
            "hard: expected valid from wgl-tpu", r)
    require(r.get("window", 0) >= n_doomed,
            "hard: the doomed ops did not pin the window", r)
    return {"ops": n_ops, "doomed": n_doomed, "wall_s": round(wall, 3),
            "configs_explored": r.get("configs-explored"),
            "max_capacity_reached": r.get("max-capacity-reached"),
            "window": r.get("window")}


# ---------------------------------------------------------------------------
# Phase 3: keyed registers
# ---------------------------------------------------------------------------

def keyed_lanes(seed: int, n: int, n_ops: int) -> List[Any]:
    """The keyed lane shape: short crash-bearing per-key histories,
    every fourth refuted by one corrupted read."""
    from jepsen_tpu.synth import cas_register_history, corrupt_reads
    hs = [cas_register_history(n_ops, concurrency=6, crash_p=0.005,
                               seed=100 + seed + i) for i in range(n)]
    for i in range(0, n, 4):
        hs[i] = corrupt_reads(hs[i], n=1, seed=seed + i)
    return hs


def phase_keyed(seed: int, n_keys: int = 512,
                n_ops: int = 200) -> Dict[str, Any]:
    from jepsen_tpu import core, independent
    from jepsen_tpu.checker import wgl_cpu
    from jepsen_tpu.checker.linearizable import linearizable
    from jepsen_tpu.history import History
    from jepsen_tpu.models import get_model

    model = get_model("cas-register")
    lanes = keyed_lanes(seed, n_keys, n_ops)
    ops = [op.with_(process=op.process + 10 * k,
                    value=independent.tuple_(k, op.value))
           for k, h in enumerate(lanes) for op in h]
    keyed = History(ops, reindex=True)
    cm = model.cpu_model()
    with ThreadPoolExecutor(max_workers=1) as host:
        want = host.submit(lambda: [
            wgl_cpu.check(cm, independent.subhistory(k, keyed))
            for k in range(n_keys)])
        t0 = time.monotonic()
        res = core.analyze(
            {"name": "chip-smoke-keyed",
             "checker": independent.checker(linearizable(model))}, keyed)
        wall = time.monotonic() - t0
        oracles = want.result()
    require(res.get("key-count") == n_keys and "disagreements" not in res,
            "keyed: missing keys or engine disagreement",
            {k: v for k, v in res.items() if k != "results"})
    refuted = 0
    for k, oracle in enumerate(oracles):
        r = res["results"][k]
        require_device_answer(r, f"keyed key {k}")
        require(same_refutation(r, oracle),
                f"keyed key {k}: differs from wgl_cpu", r, oracle.get("op"))
        refuted += r["valid"] is False
    require(res["valid"] is (refuted == 0), "keyed: merged verdict wrong",
            res.get("valid"))
    return {"keys": n_keys, "ops_each": n_ops, "refuted": refuted,
            "wall_s": round(wall, 3)}


# ---------------------------------------------------------------------------
# Phase 4: served
# ---------------------------------------------------------------------------

def phase_served(seed: int, n_small: int = 256, small_ops: int = 200,
                 big_ops: int = 10_000, n_elle: int = 32,
                 elle_txns: int = 200, threads: int = 4) -> Dict[str, Any]:
    from jepsen_tpu.checker import wgl_cpu
    from jepsen_tpu.elle import list_append
    from jepsen_tpu.models import get_model
    from jepsen_tpu.serve import CheckService
    from jepsen_tpu.synth import cas_register_history, list_append_history

    cm = get_model("cas-register").cpu_model()
    small = keyed_lanes(seed + 5000, n_small, small_ops)
    big = cas_register_history(big_ops, concurrency=8, crash_p=0.0003,
                               seed=4 + seed)
    elle = [list_append_history(n_txns=elle_txns, keys=4, concurrency=6,
                                seed=3000 + seed + i,
                                anomaly_p=0.3 if i % 8 == 0 else 0.0)
            for i in range(n_elle)]
    wgl_kw = {"kind": "wgl", "model": "cas-register"}
    elle_kw = {"kind": "elle", "workload": "list-append"}
    jobs: List[Tuple[Any, Dict[str, Any]]] = \
        [(big, wgl_kw)] + [(h, wgl_kw) for h in small] \
        + [(h, elle_kw) for h in elle]

    with ThreadPoolExecutor(max_workers=1) as host, CheckService() as svc:
        want = host.submit(lambda: [
            wgl_cpu.check(cm, h) if kw["kind"] == "wgl"
            else list_append.check(h) for h, kw in jobs])

        def submit_share(t: int) -> List[Tuple[int, Any]]:
            # every request is in the queue before any verdict is read
            return [(i, svc.submit(jobs[i][0], **jobs[i][1]))
                    for i in range(t, len(jobs), threads)]

        t0 = time.monotonic()
        with ThreadPoolExecutor(max_workers=threads) as ex:
            handles = [x for share in ex.map(submit_share, range(threads))
                       for x in share]
        results: List[Any] = [None] * len(jobs)
        for i, req in handles:
            results[i] = req.wait()
        wall = time.monotonic() - t0
        counters = svc.metrics.snapshot()["counters"]
        oracles = want.result()

    require(results[0]["valid"] is True,
            "served 10k-op request: expected valid", results[0])
    elle_refuted = 0
    for i, (d, c) in enumerate(zip(results, oracles)):
        require_device_answer(d, f"served request {i}")
        if jobs[i][1]["kind"] == "wgl":
            require(same_refutation(d, c),
                    f"served request {i}: differs from wgl_cpu", d,
                    c.get("op"))
        else:
            require(d["valid"] == c["valid"] and d.get("anomaly-types", [])
                    == c.get("anomaly-types", []),
                    f"served elle request {i}: differs from host elle",
                    d.get("anomaly-types"), c.get("anomaly-types"))
            elle_refuted += d["valid"] is False
    require(counters.get("megabatch-dispatches", 0) > 0,
            "served: no megabatch dispatch", counters)
    require(counters.get("host-fallbacks", 0) == 0,
            "served: the scheduler fell back to the host", counters)
    return {"requests": len(jobs), "wgl_small": n_small,
            "wgl_big_ops": big_ops, "elle": n_elle, "elle_refuted": elle_refuted,
            "wall_s": round(wall, 3),
            "megabatch_dispatches": counters["megabatch-dispatches"],
            "host_fallbacks": counters.get("host-fallbacks", 0)}


# ---------------------------------------------------------------------------
# Phase 5: report
# ---------------------------------------------------------------------------

def phase_report(watch: Dict[str, Any]) -> Dict[str, Any]:
    """Compile accounting since ``watch`` (a ``first_use_stats()`` taken
    before the phases: JAX's own compile and persistent-cache events, as
    the program counts them), and the cache directory in force must hold
    what this run compiled (a silently-failed cache set-up shows here)."""
    import jax

    from jepsen_tpu.obs.hist import compile_hist_stats, first_use_stats
    engines = compile_hist_stats()
    now = first_use_stats()
    d = jax.config.jax_compilation_cache_dir
    entries = ([f for f in os.listdir(d) if f.endswith("-cache")]
               if d and os.path.isdir(d) else [])
    require(bool(entries), "compile cache directory is empty or unset", d)
    return {"compile": {
                "engine_first_calls": sum(int(s.get("count", 0))
                                          for s in engines.values()),
                "engine_first_call_s": round(
                    sum(float(s.get("sum-s", 0.0))
                        for s in engines.values()), 1),
                "engines_s": {name.removeprefix("compile:"):
                              round(float(s.get("sum-s", 0.0)), 1)
                              for name, s in engines.items()},
                "backend_compiles": now["programs"] - watch["programs"],
                "backend_compile_s": round(
                    now["load_s"] - watch["load_s"], 1),
                "persistent_cache_hits":
                    now["cache_hits"] - watch["cache_hits"],
                "persistent_cache_misses":
                    now["cache_misses"] - watch["cache_misses"]},
            "cache": {"dir": d, "entries": len(entries),
                      "from_env": bool(os.environ.get(
                          "JAX_COMPILATION_CACHE_DIR"))}}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="shift every generator seed (default 0)")
    args = ap.parse_args(argv)
    t_start = time.monotonic()
    out = phase_device()
    from jepsen_tpu.obs.hist import first_use_stats
    from jepsen_tpu.ops.cache import init_compilation_cache
    init_compilation_cache()        # the program's listeners, before any op
    watch = first_use_stats()
    phases: List[Tuple[str, Callable[[int], Dict[str, Any]]]] = [
        ("offline", phase_offline), ("hard", phase_hard),
        ("keyed", phase_keyed), ("served", phase_served)]
    out["phases"] = {}
    for name, fn in phases:
        say(f"phase {name}")
        t0 = time.monotonic()
        obs = fn(args.seed)
        obs["phase_wall_s"] = round(time.monotonic() - t0, 1)
        out["phases"][name] = obs
        say(f"phase {name} ok: {obs}")
    out.update(phase_report(watch))
    out["seed"] = args.seed
    out["total_wall_s"] = round(time.monotonic() - t_start, 1)
    out["note"] = ("walls are single-run observations with start-up "
                   "(compiles or cache loads) and host-oracle contention "
                   "included; not benchmark numbers")
    print(json.dumps({"report": out}), flush=True)
    # the last line is the stamp alone: its readers take exactly these keys
    print(json.dumps({"ok": True, "device": out["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
