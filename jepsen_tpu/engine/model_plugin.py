"""The generic device-model plugin checker.

One class turns any registered :class:`~jepsen_tpu.models.base.JaxModel`
into a linearizability checker riding the shared engine substrate: the
model is constructed per check (so shape knobs can derive from the
history, bucketed onto the serve ladder for compile-cache reuse) and
handed to the :class:`~jepsen_tpu.checker.linearizable.Linearizable`
facade, which owns algorithm selection, the tpu->cpu fallback chain, and
witness recovery.  Kept out of :mod:`jepsen_tpu.engine.plugins` so the
registration seam stays import-light (checker.core imports it while
itself mid-import).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

from jepsen_tpu.checker.core import Checker
from jepsen_tpu.engine.ladder import pow2_at_least
from jepsen_tpu.history import History


class ModelPluginChecker(Checker):
    """Linearizability over a named device model.

    ``derive(history, model_kw) -> extra_kw`` lets a plugin size the
    model from the history (e.g. the fifo queue's ring capacity, bucketed
    pow2 so successive checks share one compiled engine); explicit
    ``model_kw`` entries always win over derived ones.
    """

    def __init__(self, model_name: str,
                 model_kw: Optional[Dict[str, Any]] = None,
                 derive: Optional[Callable[[History, Dict[str, Any]],
                                           Dict[str, Any]]] = None,
                 algorithm: Optional[str] = None, **engine_opts):
        self.model_name = model_name
        self.model_kw = dict(model_kw or {})
        self.derive = derive
        self.algorithm = algorithm
        self.engine_opts = engine_opts

    def check(self, test, history: History, opts=None) -> Dict[str, Any]:
        from jepsen_tpu.checker.linearizable import Linearizable
        from jepsen_tpu.models import get_model
        kw = dict(self.model_kw)
        if self.derive is not None:
            derived = self.derive(history, kw)
            for k, v in derived.items():
                kw.setdefault(k, v)
        model = get_model(self.model_name, **kw)
        res = Linearizable(model, self.algorithm,
                           **self.engine_opts).check(test, history, opts)
        res.setdefault("model", model.name)
        return res


def derive_queue_slots(history: History,
                       kw: Dict[str, Any]) -> Dict[str, Any]:
    """Ring capacity for the fifo-queue device tier: at least the number
    of enqueue invocations (a linearization can never hold more), rounded
    onto the pow2 ladder (floor 8) so queue checks of similar size share
    one compiled engine shape."""
    if "slots" in kw:
        return {}
    n_enq = sum(1 for op in history
                if op.invoke_ and op.f == "enqueue")
    n_enq = max(n_enq, sum(1 for op in history
                           if not op.invoke_ and op.f == "enqueue"))
    return {"slots": pow2_at_least(n_enq, 8)}
