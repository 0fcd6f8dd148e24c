"""Per-lane verdict assembly: device flags -> elle-shaped result map.

The device only answers "is there a cycle (under mask X)?" — everything
human-readable comes from the CPU machinery, run *only when needed*:

- acyclic lane: no cycle search at all.  The host anomalies from
  ``analyze`` (G1a/G1b/duplicates/...) plus empty cycle families are
  exactly what the CPU checker would have produced (its searches find
  nothing in an acyclic graph), so the results agree without the work;
  a valid one never builds the graph as an object either.
- cyclic lane: materialize the realtime layer (if strict mode) and run
  the same ``collect_cycle_anomalies`` suite over the same graph the CPU
  checker uses — identical witnesses, identical labels.
- flags unavailable (device error / engine="cpu"): recovery runs
  unconditionally; the result is the CPU checker's, reached through the
  engine's degradation chain.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import numpy as np

from jepsen_tpu.elle.graph import SearchBudget, edge_list
from jepsen_tpu.elle.list_append import (CYCLE_SEVERITY, add_realtime_edges,
                                         collect_cycle_anomalies,
                                         finish_result)
from jepsen_tpu.elle_tpu.closure import FLAG_NAMES
from jepsen_tpu.elle_tpu.encode import EncodedHistory
from jepsen_tpu.obs.recorder import span

ANALYZER = "elle-tpu"

#: every label ``collect_cycle_anomalies`` files a cycle under
CYCLE_TYPES = frozenset(label + suffix for label in CYCLE_SEVERITY
                        for suffix in ("", "-realtime"))


def finish_lane(enc: EncodedHistory,
                flags: Optional[np.ndarray],
                realtime: bool,
                consistency_models: Sequence[str],
                budget: Optional[SearchBudget] = None) -> Dict[str, Any]:
    """One lane's result map from its encoding and device flag vector
    (``flags=None`` means "no device verdict — search unconditionally")."""
    a = enc.finish_analysis()
    truncated = False
    recover = flags is None or bool(flags[0])
    if recover:
        with span("elle.recover", txns=a.count, realtime=realtime) as sp:
            if realtime:
                add_realtime_edges(a.graph, a.oks, a.pairs, budget=budget)
            truncated = collect_cycle_anomalies(a.graph, a.txn_of,
                                                a.anomalies, budget=budget)
            sp.set(cyclic=any(t in a.anomalies for t in CYCLE_TYPES),
                   truncated=truncated)
    with span("elle.render", txns=a.count):
        res = finish_result(a.anomalies, consistency_models, a.count,
                            truncated=truncated)
        res["analyzer"] = ANALYZER
        if flags is not None:
            res["device-flags"] = {name: bool(v)
                                   for name, v in zip(FLAG_NAMES, flags)}
        # Complete edge list for artifact rendering (popped by
        # elle.render.write_artifacts, which writes none for a valid
        # result: so an acyclic, valid lane builds no graph).  On an
        # acyclic strict-mode lane the dense realtime layer was never
        # materialized host-side — the list then carries the ww/wr/rw core
        # only.
        if res["valid"] is not True:
            res["edges-full"] = edge_list(a.graph)
    return res
