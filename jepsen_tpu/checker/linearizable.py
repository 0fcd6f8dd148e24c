"""The linearizable checker facade — algorithm selection and competition.

Parity: jepsen.checker/linearizable (checker.clj:185-216), which dispatches
on ``:algorithm`` to knossos's linear/wgl/competition solvers.  Here the
algorithms are:

- ``"tpu"``          — the device engine (wgl_tpu), requires a JaxModel;
- ``"cpu"``/``"wgl"`` — the host BFS oracle (wgl_cpu), any Model;
- ``"linear"``       — the memoized DFS solver (linear_cpu), any Model —
  the knossos ``linear`` role, algorithmically distinct from wgl;
- ``"competition"``  — race device + both host solvers on threads, first
  definite verdict wins (knossos.competition parity — the reference races
  its two CPU algorithms the same way; also the fallback tier for models
  with no device encoding, SURVEY.md §7 hard-parts);
- default: "tpu" when the model has a device tier, else "cpu".
"""

from __future__ import annotations

import atexit
import threading
from typing import Any, Dict, List, Optional, Union

from jepsen_tpu.checker import linear_cpu, wgl_cpu, wgl_tpu
from jepsen_tpu.checker.core import Checker, UNKNOWN
from jepsen_tpu.engine.witness import WITNESS_BUDGET, cpu_witness
from jepsen_tpu.history import History, Op
from jepsen_tpu.models.base import JaxModel, Model
from jepsen_tpu.obs.recorder import carry


# Losing competition racers still draining after their verdict was beaten.
# Joined (bounded) at interpreter exit: tearing down XLA under a daemon
# thread mid-dispatch aborts the process ("FATAL: exception not rethrown"),
# while a plain non-daemon thread would hang exit forever if a device
# transfer wedges.  Cancellation makes the join fast in practice —
# losers exit at their next chunk boundary / closure round.
_stragglers: List[threading.Thread] = []
_stragglers_lock = threading.Lock()


@atexit.register
def _drain_stragglers(timeout: float = 30.0) -> None:
    import time
    deadline = time.monotonic() + timeout
    with _stragglers_lock:
        ts = list(_stragglers)
    for t in ts:
        t.join(timeout=max(0.0, deadline - time.monotonic()))


class Linearizable(Checker):
    def __init__(self, model: Union[JaxModel, Model],
                 algorithm: Optional[str] = None, **engine_opts):
        self.model = model
        self.algorithm = algorithm
        self.engine_opts = engine_opts

    def _cpu_model(self) -> Optional[Model]:
        if isinstance(self.model, Model):
            return self.model
        if isinstance(self.model, JaxModel) and self.model.cpu_model:
            return self.model.cpu_model()
        return None

    def _jax_model(self) -> Optional[JaxModel]:
        return self.model if isinstance(self.model, JaxModel) else None

    def check(self, test, history: History, opts=None):
        algo = self.algorithm
        jm, cm = self._jax_model(), self._cpu_model()
        if algo is None:
            algo = "tpu" if jm is not None else "cpu"
        if algo == "tpu":
            if jm is None:
                return {"valid": UNKNOWN,
                        "error": "model has no device tier; use cpu"}
            try:
                # The fission layer IS wgl_tpu.check below the threshold;
                # above it, capacity overflow splits the search instead of
                # degrading to unknown (engine.fission).  Callers opt out
                # per-check with fission=False in engine_opts.
                from jepsen_tpu.engine import fission
                res = fission.check(jm, history, **self.engine_opts)
            except Exception as e:  # noqa: BLE001
                res = self._tpu_fallback(history, cm, e)
        elif algo in ("cpu", "linear", "wgl"):
            if cm is None:
                return {"valid": UNKNOWN, "error": "no host-tier model"}
            solver = linear_cpu if algo == "linear" else wgl_cpu
            try:
                res = solver.check(cm, history)
            except wgl_cpu.SearchExploded as e:
                return {"valid": UNKNOWN, "error": str(e)}
        elif algo == "competition":
            res = self._competition(test, history)
        else:
            return {"valid": UNKNOWN, "error": f"unknown algorithm {algo!r}"}
        if res.get("valid") is False:
            self.explain_refutation(test, history, res, opts)
        return res

    def explain_refutation(self, test, history: History,
                           res: Dict[str, Any], opts=None) -> None:
        """The tail of every refutation, in place: the host-confirmed
        witness where a device engine left the refuting op alone, then
        linear.svg.  A device lane knows which op emptied its frontier,
        not the path there (engine.witness); ``wgl_tpu.check`` attaches
        the host oracle's witness itself, the batched lanes do not, so
        ``IndependentChecker`` brings each key ``check_batch`` refuted
        here with the key's sub-history, under this checker's ``explain``
        and ``witness_budget``.  A host solver's refutation is its own
        witness."""
        jm = self._jax_model()
        if ("witness" not in res and res.get("op")
                and str(res.get("analyzer", "")).startswith("wgl-tpu")
                and self.engine_opts.get("explain", True)
                and jm is not None and jm.cpu_model is not None):
            res["witness"] = cpu_witness(
                jm, history, Op.from_dict(res["op"]),
                self.engine_opts.get("witness_budget", WITNESS_BUDGET))
        self._render(test, history, res, opts)

    def _tpu_fallback(self, history: History, cm: Optional[Model],
                      exc: Exception) -> Dict[str, Any]:
        """Degradation chain for a crashed device engine (robustness tier
        of checker.clj:185-216's competition: never let a device error
        decide a verdict).  A TPU failure — XLA OOM, runtime wedge, device
        loss — says nothing about the *history*, so instead of surfacing
        the crash as the result we fall back to the host BFS oracle
        (wgl_cpu), annotating the verdict with the chain it travelled
        (the engine.fallback discipline, shared with the elle engine and
        the serve scheduler's host-fallback cells).  Only when the CPU
        tier is missing or itself gives up (its state set exceeds the
        budget) does the verdict degrade to UNKNOWN, and then it carries
        partial-search stats so the operator can tell \"checker
        overwhelmed\" from \"history lost\"."""
        from jepsen_tpu.engine.fallback import (
            annotate_fallback, chain_entry, warn_fallback,
        )
        entry = chain_entry("wgl-tpu", exc)
        chain: List[Dict[str, Any]] = [entry]
        warn_fallback("wgl-tpu", "wgl-cpu", exc)
        if cm is None:
            return {"valid": UNKNOWN,
                    "error": "device engine failed and model has no "
                             f"host tier: {exc}",
                    "fallback-chain": chain}
        try:
            res = wgl_cpu.check(cm, history)
        except wgl_cpu.SearchExploded as e2:
            chain.append({"solver": "wgl-cpu", "error": str(e2)})
            return {"valid": UNKNOWN, "error": str(e2),
                    "fallback-chain": chain,
                    "partial-search": {"configs-explored": e2.n,
                                       "exhausted": False}}
        except Exception as e2:  # noqa: BLE001
            chain.append(chain_entry("wgl-cpu", e2))
            return {"valid": UNKNOWN,
                    "error": f"device engine and host oracle both "
                             f"failed: {exc}; {e2}",
                    "fallback-chain": chain}
        annotate_fallback(res, "wgl-tpu", "wgl-cpu", entry, chain)
        res.setdefault("solver", "wgl-cpu")
        return res

    def _render(self, test, history, res, opts) -> None:
        """Write linear.svg next to the results (knossos.linear.report
        parity, checker.clj:207-211).  Best-effort: rendering trouble must
        never mask the verdict."""
        import os
        d = (opts or {}).get("store_dir") or (test or {}).get("store_dir")
        if not d:
            return
        try:
            from jepsen_tpu.checker.render import render_analysis
            path = render_analysis(history, res, os.path.join(d, "linear.svg"))
            if path:
                res["render"] = path
        except Exception as e:  # noqa: BLE001
            res["render-error"] = str(e)

    def _competition(self, test, history):
        """Race the device engine and BOTH host solvers (BFS wgl + DFS
        linear — three algorithmically distinct searches); the first
        *definite* verdict (valid True/False) wins and the losers are
        cancelled.  An UNKNOWN from one racer — e.g. a host solver
        exploding early — must NOT mask a definite answer still coming from
        another; only when every racer finishes indefinite does the race
        report unknown.  Parity: knossos.competition via
        checker.clj:199-202, which races knossos's linear and wgl solvers
        the same way, takes the first non-:unknown analysis and cancels the
        losing futures."""
        jm, cm = self._jax_model(), self._cpu_model()
        if jm is None and cm is None:
            return {"valid": UNKNOWN, "error": "no model tier available"}
        if jm is None or cm is None:
            # only one tier available: no cross-tier race (a cm-only model
            # still races its two host algorithms below when jm is None)
            if cm is None:
                self2 = Linearizable(self.model, None, **self.engine_opts)
                return self2.check(test, history)
        done = threading.Event()
        cancel = threading.Event()
        lock = threading.Lock()
        results: Dict[str, Any] = {"indefinite": {}}

        def post(solver: str, r: Dict[str, Any]) -> None:
            definite = r.get("valid") in (True, False)
            with lock:
                if definite and "winner" not in results:
                    results["winner"] = {**r, "solver": solver}
                    cancel.set()   # stop the loser's search
                    done.set()
                elif definite:
                    # A second definite verdict: surface disagreement (a
                    # solver bug!) instead of silently discarding it.  The
                    # winner dict may already be returned to the caller, so
                    # never mutate it here — attach if the race is still
                    # open, log otherwise.
                    w = results["winner"]
                    if w.get("valid") != r.get("valid"):
                        if results.get("returned"):
                            import logging
                            logging.getLogger(__name__).error(
                                "solver disagreement after verdict: "
                                "%s=%r vs %s=%r", w.get("solver"),
                                w.get("valid"), solver, r.get("valid"))
                        else:
                            w["disagreement"] = {**r, "solver": solver}
                else:
                    results["indefinite"][solver] = r
                    if len(results["indefinite"]) == n_racers:
                        done.set()  # all indefinite: race is over anyway

        def run_tpu():
            try:
                r = wgl_tpu.check(jm, history, cancel=cancel,
                                  **self.engine_opts)
            except Exception as e:  # noqa: BLE001
                r = {"valid": UNKNOWN, "error": str(e)}
            post("tpu", r)

        def run_host(name, solver):
            def go():
                try:
                    r = solver.check(cm, history, cancel=cancel)
                except wgl_cpu.Cancelled:
                    r = {"valid": UNKNOWN, "cancelled": True}
                except wgl_cpu.SearchExploded as e:
                    r = {"valid": UNKNOWN, "error": str(e)}
                except Exception as e:  # noqa: BLE001
                    r = {"valid": UNKNOWN, "error": str(e)}
                post(name, r)
            return go

        ts = []
        if jm is not None:
            ts.append(threading.Thread(target=carry(run_tpu), daemon=True))
        if cm is not None:
            ts.append(threading.Thread(
                target=carry(run_host("cpu", wgl_cpu)), daemon=True))
            ts.append(threading.Thread(
                target=carry(run_host("linear", linear_cpu)), daemon=True))
        n_racers = len(ts)
        for t in ts:
            t.start()
        done.wait()
        cancel.set()  # both-indefinite path never set it
        for t in ts:  # losers usually exit within one chunk/closure round
            t.join(timeout=0.2)
        with _stragglers_lock:
            _stragglers[:] = [t for t in _stragglers if t.is_alive()]
            _stragglers.extend(t for t in ts if t.is_alive())
        with lock:
            results["returned"] = True
            if "winner" in results:
                # Snapshot: a straggler must not mutate the caller's dict.
                return dict(results["winner"])
            # Both solvers indefinite: report the combined unknown.
            return {"valid": UNKNOWN, "solver": "competition",
                    "solvers": dict(results["indefinite"])}


def linearizable(model, algorithm: Optional[str] = None, **kw) -> Checker:
    return Linearizable(model, algorithm, **kw)
