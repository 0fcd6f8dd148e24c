"""The Elle transactional-anomaly checkers as checker plugins.

Parity: the reference composes elle's cycle checkers into a test's
checker map (jepsen/src/jepsen/tests/cycle/append.clj:15-21, wr.clj:9-25);
here ``ElleChecker`` wraps the elle_tpu engine (device tier with CPU
degradation chain — see jepsen_tpu.elle_tpu) behind the standard Checker
protocol so it composes with checker.core's battery, rides ``check_safe``
budget/``duration-s`` accounting, and writes the ``elle/`` artifact
directory into the store dir like the reference's ``:directory`` option.

Registered (checker.core registry): ``elle-list-append``,
``elle-rw-register``, plus ``-cpu`` variants pinning the oracle path.

Budget plumbing: ``check_safe``'s wall-clock budget kills the checker
thread from outside; this checker *also* threads the same budget into the
engine as a SearchBudget deadline, so cycle recovery degrades gracefully
(``cycle-search-truncated``, clean verdicts -> unknown) before the
outside kill ever fires.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from jepsen_tpu.checker.core import Checker
from jepsen_tpu.elle import consistency, render
from jepsen_tpu.history import History


class ElleChecker(Checker):
    def __init__(self, workload: str = "list-append",
                 engine: str = "auto",
                 realtime: Optional[bool] = None,
                 consistency_models: Optional[Sequence[str]] = None,
                 budget_s: Optional[float] = None,
                 **workload_kw):
        self.workload = workload
        self.engine = engine
        # the realtime order is part of the graph exactly when a model
        # that speaks of it is asked for, unless the caller says otherwise
        self.realtime = (consistency.needs_realtime(consistency_models)
                         if realtime is None else realtime)
        if (workload == "rw-register"
                and workload_kw.get("linearizable_keys") is None):
            # strict serializability holds each key linearizable, so a
            # register's version order may follow its writes' realtime
            # order wherever that model is judged (the engine's default
            # with the realtime order and no models named)
            workload_kw["linearizable_keys"] = (
                self.realtime if consistency_models is None
                else consistency.needs_realtime(consistency_models))
        self.consistency_models = consistency_models
        self.budget_s = budget_s
        self.workload_kw = workload_kw

    def _budget_s(self, test, opts) -> Optional[float]:
        if self.budget_s is not None:
            return self.budget_s
        b = (opts or {}).get("budget_s")
        if b is None:
            b = (test or {}).get("checker_budget_s")
        return b

    def check(self, test, history: History,
              opts: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        from jepsen_tpu import elle_tpu
        res = elle_tpu.check(history,
                             workload=self.workload,
                             engine=self.engine,
                             realtime=self.realtime,
                             consistency_models=self.consistency_models,
                             budget_s=self._budget_s(test, opts),
                             **self.workload_kw)
        render.write_artifacts(test, res, opts)
        return res


class ElleListAppend(ElleChecker):
    def __init__(self, **kw):
        kw.setdefault("workload", "list-append")
        super().__init__(**kw)


class ElleRwRegister(ElleChecker):
    def __init__(self, **kw):
        kw.setdefault("workload", "rw-register")
        super().__init__(**kw)


def elle_list_append(**kw) -> Checker:
    return ElleListAppend(**kw)


def elle_rw_register(**kw) -> Checker:
    return ElleRwRegister(**kw)
