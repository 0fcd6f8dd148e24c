"""History -> dense device tensors for the elle_tpu engine.

The encoder is deliberately thin: it runs the *CPU checker's own* host
pass (``elle.list_append.analyze`` / ``elle.rw_register.analyze``) and
merely re-shapes its dependency graph into fixed-kind edge arrays, plus
the invoke/complete index vectors the device needs to rebuild the
realtime order as a broadcast comparison.  Sharing the host pass is the
parity argument's foundation — both tiers literally analyze the same
``Analysis`` object (see the package docstring).  The list-append pass
comes in two halves: its ``Dependencies`` are enough to encode, and the
rest (``analysis_of``) can follow the dispatch.

Encoding:

- ``src/dst [3, E] int32`` — per-kind (ww, wr, rw) edge endpoints, padded
  with ``-1``.  The device reconstructs each adjacency layer as
  ``one_hot(src).T @ one_hot(dst)`` (a ``-1`` one-hots to a zero row, so
  padding vanishes); a matmul-based build sidesteps the vmapped
  bool-scatter miscompile documented at parallel/batch.py (the
  MAX_LANES_PER_GROUP cap) entirely.
- ``invoke/complete [N] int32`` — each txn's invocation/completion index
  in the client subhistory.  ``invoke = -1`` marks an unknown invocation
  (no realtime edges *into* that txn, matching the CPU checker's
  ``inv >= 0`` guard); padding rows get ``complete = COMPLETE_PAD`` (no
  realtime edges *out of* them either).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Optional, Tuple, Union

import numpy as np

from jepsen_tpu.elle import list_append, rw_register
from jepsen_tpu.elle.graph import edge_list
from jepsen_tpu.elle.list_append import Analysis, Dependencies
from jepsen_tpu.history import History

#: edge-kind layer order of the ``src``/``dst`` arrays.
KINDS = list_append.EDGE_KINDS

WORKLOADS = ("list-append", "rw-register")

#: completion index for padding txn slots: later than any real invocation,
#: so a padded row emits no realtime edge.
COMPLETE_PAD = np.int32(2**30)


@dataclass
class EncodedHistory:
    """One history's device encoding plus the host ``Analysis`` it came
    from (kept for witness recovery — the device only answers booleans).
    An encoding made of a list-append history's ``Dependencies`` holds
    those, and gets its ``analysis`` from :meth:`finish_analysis`: the
    second half of the host pass, which the engine runs once the lane's
    closures are on their way."""
    analysis: Optional[Analysis]
    workload: str
    src: np.ndarray        # [len(KINDS), E] int32, -1-padded
    dst: np.ndarray        # [len(KINDS), E] int32, -1-padded
    invoke: np.ndarray     # [N] int32, -1 = unknown invocation
    complete: np.ndarray   # [N] int32
    n: int                 # ok transactions
    dependencies: Optional[Dependencies] = None
    #: ``edge_list`` of the analysis' graph as :meth:`finish_analysis`
    #: left it (ww/wr/rw only), for a lane that needs no recovery
    edge_list: Optional[List[Tuple[Any, Any, List[str]]]] = None

    @property
    def n_edges(self) -> int:
        return int((self.src >= 0).sum())

    def finish_analysis(self) -> Analysis:
        if self.analysis is None:
            self.analysis = list_append.analysis_of(self.dependencies)
        if self.edge_list is None:
            self.edge_list = edge_list(self.analysis.graph)
        return self.analysis


def analyze(history: History, workload: str = "list-append",
            **workload_kw) -> Analysis:
    """Dispatch to the workload's host pass."""
    if workload == "list-append":
        return list_append.analyze(history, **workload_kw)
    if workload == "rw-register":
        return rw_register.analyze(history, **workload_kw)
    raise ValueError(f"unknown elle workload {workload!r}; "
                     f"known: {WORKLOADS}")


def dependencies(history: History, workload: str = "list-append",
                 **workload_kw) -> Union[Dependencies, Analysis]:
    """As much of the workload's host pass as has to precede the device:
    a list-append history's ``Dependencies``; a register history's pass is
    one piece, so it is the whole ``Analysis``."""
    if workload == "list-append":
        return list_append.dependencies(history, **workload_kw)
    return analyze(history, workload, **workload_kw)


def encode(history: History, workload: str = "list-append",
           **workload_kw) -> EncodedHistory:
    """The whole host pass, encoded (``analysis`` is there)."""
    enc = encode_analysis(dependencies(history, workload, **workload_kw),
                          workload)
    enc.finish_analysis()
    return enc


def encode_analysis(a: Union[Dependencies, Analysis],
                    workload: str) -> EncodedHistory:
    if isinstance(a, Dependencies):
        flat = np.fromiter(a.edges, np.int64, len(a.edges))
    else:
        flat = np.fromiter(
            (x for s, bs in a.graph.out.items() for d, ks in bs.items()
             for k in ks if k in KINDS for x in (s, d, KINDS.index(k))),
            np.int64)
    flat = flat.reshape(-1, 3)
    # a pair is one cell of its kind's layer, however often it was inferred
    pair = flat[:, 0] << 32 | flat[:, 1]
    per = [np.unique(pair[flat[:, 2] == i]) for i in range(len(KINDS))]
    e = max(1, max(len(p) for p in per))
    src = np.full((len(KINDS), e), -1, np.int32)
    dst = np.full((len(KINDS), e), -1, np.int32)
    for i, p in enumerate(per):
        src[i, :len(p)] = p >> 32
        dst[i, :len(p)] = p & 0xFFFFFFFF
    n = a.count
    invoke = np.full(max(1, n), -1, np.int32)
    complete = np.full(max(1, n), COMPLETE_PAD, np.int32)
    if n:
        done = np.fromiter((i for i, _ in a.oks), np.int64, n)
        complete[:n] = done
        invoke[:n] = np.maximum(np.asarray(a.pairs)[done], -1)
    half = isinstance(a, Dependencies)
    return EncodedHistory(analysis=None if half else a,
                          dependencies=a if half else None,
                          workload=workload, src=src, dst=dst,
                          invoke=invoke, complete=complete, n=n)
