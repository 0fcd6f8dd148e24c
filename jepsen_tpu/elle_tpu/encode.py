"""History -> dense device tensors for the elle_tpu engine.

The encoder is deliberately thin: it runs the *CPU checker's own* host
pass (``elle.list_append`` / ``elle.rw_register``) and merely re-shapes
its dependency edges into fixed-kind edge arrays, plus the
invoke/complete index vectors the device needs to rebuild the realtime
order as a broadcast comparison.  Sharing the host pass is the parity
argument's foundation — both tiers literally analyze the same
``Analysis`` object (see the package docstring).  Each workload's pass
comes in two halves: its ``Dependencies`` (the ok transactions and the
ww/wr/rw edges as one flat array) are enough to encode, and the rest
(the workload's ``analysis_of``: the host anomalies) can follow the
dispatch.  The graph as an object (``Analysis.graph``) is built from the
same edges only for a lane that asks for it: a witness search, or an
invalid result's artifacts.

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
from typing import Optional, Union

import numpy as np

from jepsen_tpu.elle import list_append, rw_register
from jepsen_tpu.elle.list_append import Analysis
from jepsen_tpu.history import History

#: edge-kind layer order of the ``src``/``dst`` arrays.
KINDS = list_append.EDGE_KINDS

#: each workload's host pass: its first half, then its second
PASSES = {"list-append": (list_append.dependencies, list_append.analysis_of),
          "rw-register": (rw_register.dependencies, rw_register.analysis_of)}

Dependencies = Union[list_append.Dependencies, rw_register.Dependencies]

#: completion index for padding txn slots: later than any real invocation,
#: so a padded row emits no realtime edge.
COMPLETE_PAD = np.int32(2**30)


@dataclass
class EncodedHistory:
    """One history's device encoding, made of the first half of its host
    pass (``dependencies``), and the host ``Analysis`` (kept for witness
    recovery — the device only answers booleans), which
    :meth:`finish_analysis` makes: the second half of the host pass, which
    the engine runs once the lane's closures are on their way."""
    dependencies: Dependencies
    workload: str
    src: np.ndarray        # [len(KINDS), E] int32, -1-padded
    dst: np.ndarray        # [len(KINDS), E] int32, -1-padded
    invoke: np.ndarray     # [N] int32, -1 = unknown invocation
    complete: np.ndarray   # [N] int32
    n: int                 # ok transactions
    analysis: Optional[Analysis] = None

    @property
    def n_edges(self) -> int:
        return int((self.src >= 0).sum())

    def finish_analysis(self) -> Analysis:
        if self.analysis is None:
            self.analysis = PASSES[self.workload][1](self.dependencies)
        return self.analysis


def dependencies(history: History, workload: str = "list-append",
                 **workload_kw) -> Dependencies:
    """As much of the workload's host pass as has to precede the device:
    its ``Dependencies`` (``rw_register``'s take ``sequential_keys`` and
    ``linearizable_keys``)."""
    if workload not in PASSES:
        raise ValueError(f"unknown elle workload {workload!r}; "
                         f"known: {tuple(PASSES)}")
    return PASSES[workload][0](history, **workload_kw)


def encode(history: History, workload: str = "list-append",
           **workload_kw) -> EncodedHistory:
    """The whole host pass, encoded (``analysis`` is there)."""
    enc = encode_analysis(dependencies(history, workload, **workload_kw),
                          workload)
    enc.finish_analysis()
    return enc


def encode_analysis(a: Dependencies, workload: str) -> EncodedHistory:
    edges = a.edges          # a list (list-append) or an array (rw-register)
    if not isinstance(edges, np.ndarray):
        edges = np.fromiter(edges, np.int64, len(edges))
    flat = edges.reshape(-1, 3)
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
    return EncodedHistory(dependencies=a, workload=workload, src=src,
                          dst=dst, invoke=invoke, complete=complete, n=n)
