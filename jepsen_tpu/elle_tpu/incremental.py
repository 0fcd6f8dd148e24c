"""Incremental elle closure: extend the previous fixpoint, don't restart.

The epoch monitor's elle side re-checks a growing prefix every epoch.
The cold kernel (:mod:`jepsen_tpu.elle_tpu.closure`) closes each epoch's
adjacency from scratch — boolean squarings of an ``[N, N]`` matrix
until the whole graph's longest shortest path is closed, up to
``ceil(log2 N)`` — so per-epoch cost grows with history length.  But
the closure is monotone under edge appends: for ``S ⊇ A``,

    closure(S) = closure(closure(A) ∨ S)

so seeding the squaring loop with the *previous epoch's closed matrix*
OR'd over the current layers converges in however many doublings the
NEW paths need (typically one or two), not the whole graph's.  The three
closed matrices (full / nonrw / g0) stay resident on device between
epochs; per-anomaly flags are read off the extended matrices exactly as
the cold lane computes them, and the result dict is assembled by the
same ``finish_lane`` the cold engine uses — identical anomaly sets by
construction.

When warm seeding is *not* provably sound, the engine resets cold and
says so in its counters.  The guards, checked per epoch against the
stored state:

- node-ordinal stability — ``encode``'s node order is the OK-txn
  enumeration of the client subhistory, append-only for an append-only
  op stream, and cut ``info`` txns are never graph nodes; the stored
  ``invoke``/``complete`` prefixes must match exactly;
- edge-implication — the soundness condition is per-lane closure
  containment, ``cl(A) ⊆ cl(S)``, and the direct edge sets do NOT grow
  monotonically: a new read refines a key's version order, replacing an
  adjacent-pair ww edge ``A→C`` with ``A→B, B→C`` (and re-targeting rw
  antidependencies).  So every previous direct edge must either survive
  or be *implied by a same-lane path* in today's graph: a lost ww edge
  needs a ww path (it sits in all three lanes, g0 included), a lost wr
  edge a ww∪wr path (the nonrw lane), a lost rw edge a ww∪wr∪rw path
  (rw edges only ever enter the full lane — the rw matrix itself is
  rebuilt fresh each epoch, never carried).  Closure is monotone and
  idempotent, so implied-per-lane direct edges give
  ``cl_lane(A) ⊆ cl_lane(cl_lane(S)) = cl_lane(S)`` exactly.  A lost
  edge with no implying path (a genuinely reordered version graph,
  e.g. an incompatible-order anomaly) fails the guard and resets cold.

The host analysis + encode still run over the full prefix each epoch
(an O(prefix) host residual — the device closure is what this module
makes incremental); ``JTPU_STREAM_ORACLE=1`` additionally runs the cold
device kernel every epoch and prefers its flags on any mismatch (the
parity oracle the fuzz tests and the smoke job use).
"""

from __future__ import annotations

import math
import os
from functools import lru_cache
from typing import Any, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jepsen_tpu.elle_tpu.closure import _layer, lane_flags_fn
from jepsen_tpu.elle_tpu.encode import KINDS, EncodedHistory, encode
from jepsen_tpu.engine.budget import Deadline
from jepsen_tpu.engine.ladder import MIN_N_BUCKET, pad_words, pow2_at_least
from jepsen_tpu.monitor.epochs import ElleEpochEngine


def oracle_enabled() -> bool:
    return os.environ.get("JTPU_STREAM_ORACLE", "") not in ("", "0",
                                                            "false", "off")


@lru_cache(maxsize=None)
def _seed_fn(n_pad: int, realtime: bool):
    """Jitted seeding: rebuild today's adjacency layers and OR the
    previous epoch's closed matrices on top.  One trace per
    (n_pad, realtime); the edge axis retraces per 64-quantized e_pad."""

    def seed(src, dst, invoke, complete, prev_full, prev_nonrw, prev_g0):
        ww = _layer(src[0], dst[0], n_pad)
        wr = _layer(src[1], dst[1], n_pad)
        rw = _layer(src[2], dst[2], n_pad)
        if realtime:
            rt = ((complete[:, None] < invoke[None, :])
                  & (invoke[None, :] >= 0)).astype(jnp.float32)
        else:
            rt = jnp.zeros((n_pad, n_pad), jnp.float32)
        nonrw = jnp.minimum(ww + wr + rt, 1.0)
        full = jnp.minimum(nonrw + rw, 1.0)
        g0 = jnp.minimum(ww + rt, 1.0)
        return (jnp.minimum(full + prev_full, 1.0),
                jnp.minimum(nonrw + prev_nonrw, 1.0),
                jnp.minimum(g0 + prev_g0, 1.0),
                rw)

    return jax.jit(seed)


def set_cells(r: jnp.ndarray) -> jnp.ndarray:
    """The nonzero cells of a 0/1 matrix, as an exact int32 count."""
    return jnp.sum(r > 0, dtype=jnp.int32)


@lru_cache(maxsize=None)
def _square_fn(n_pad: int):
    """Two path-doubling rounds over the three matrices plus their counts
    of set cells (the host's convergence probe: R only grows, so a 0/1
    matrix is a fixpoint of ``min(R + R@R, 1)`` iff its count stops
    growing; an int32 count, exact as the kernel's cell comparison is,
    since a float32 sum cannot see a few more cells past 2^24)."""

    def sq(a, b, c):
        for _ in range(2):
            a = jnp.minimum(a + a @ a, 1.0)
            b = jnp.minimum(b + b @ b, 1.0)
            c = jnp.minimum(c + c @ c, 1.0)
        return a, b, c, jnp.stack([set_cells(a), set_cells(b),
                                   set_cells(c)])

    return jax.jit(sq)


@lru_cache(maxsize=None)
def _flags_fn(n_pad: int):
    def flags(cl_full, cl_nonrw, cl_g0, rw):
        return jnp.stack([jnp.trace(cl_full) > 0,
                          jnp.trace(cl_g0) > 0,
                          jnp.trace(cl_nonrw) > 0,
                          jnp.sum(rw * cl_nonrw.T) > 0])

    return jax.jit(flags)


class _ClosureState:
    """The previous epoch's device-resident fixpoint plus the host-side
    facts that prove it is still extendable."""

    __slots__ = ("n", "n_pad", "edges", "invoke", "complete",
                 "cl_full", "cl_nonrw", "cl_g0")

    def __init__(self, n, n_pad, edges, invoke, complete,
                 cl_full, cl_nonrw, cl_g0):
        self.n = n
        self.n_pad = n_pad
        self.edges = edges
        self.invoke = invoke
        self.complete = complete
        self.cl_full = cl_full
        self.cl_nonrw = cl_nonrw
        self.cl_g0 = cl_g0


def _edge_set(enc: EncodedHistory) -> Set[Tuple[int, int, int]]:
    out = set()
    for i in range(len(KINDS)):
        for s, d in zip(enc.src[i], enc.dst[i]):
            if s >= 0:
                out.add((i, int(s), int(d)))
    return out


#: per-kind edge universes an implying path may use (KINDS order is
#: ww, wr, rw): a lost ww edge is in every lane including g0, so only a
#: ww path implies it everywhere; wr sits in nonrw and full; rw only in
#: the full lane.
_IMPLY_KINDS = {0: (0,), 1: (0, 1), 2: (0, 1, 2)}


def _lost_edges_implied(lost: Set[Tuple[int, int, int]],
                        edges: Set[Tuple[int, int, int]]) -> bool:
    """True when every lost previous direct edge is implied by a
    same-lane path in today's direct graph — the refinement case
    (version orders gaining intermediate writes), not a reorder."""
    adj: Dict[int, Dict[int, List[int]]] = {k: {} for k in _IMPLY_KINDS}
    for k, s, d in edges:
        adj[k].setdefault(s, []).append(d)
    for k, s, d in lost:
        lanes = _IMPLY_KINDS[k]
        seen = {s}
        stack = [s]
        found = False
        while stack and not found:
            u = stack.pop()
            for kk in lanes:
                for v in adj[kk].get(u, ()):
                    if v == d:
                        found = True
                        break
                    if v not in seen:
                        seen.add(v)
                        stack.append(v)
                if found:
                    break
        if not found:
            return False
    return True


def _pad_edges(enc: EncodedHistory) -> Tuple[np.ndarray, np.ndarray]:
    e_pad = pad_words(max(1, enc.src.shape[1]), 64)
    src = np.full((len(KINDS), e_pad), -1, np.int32)
    dst = np.full((len(KINDS), e_pad), -1, np.int32)
    src[:, :enc.src.shape[1]] = enc.src
    dst[:, :enc.dst.shape[1]] = enc.dst
    return src, dst


def _grow(mat, n_pad: int):
    """Re-pad a closed [m, m] matrix top-left into an [n_pad, n_pad]
    zero matrix when the stream climbs an n rung."""
    m = mat.shape[0]
    if m == n_pad:
        return mat
    return jnp.zeros((n_pad, n_pad), jnp.float32).at[:m, :m].set(mat)


class IncrementalElleEngine(ElleEpochEngine):
    """ElleEpochEngine whose device closure extends across epochs."""

    def __init__(self, workload: str = "list-append",
                 realtime: bool = False, service=None,
                 budget_s: Optional[float] = None):
        super().__init__(workload=workload, realtime=realtime,
                         service=service, budget_s=budget_s)
        self._state: Optional[_ClosureState] = None
        self.resets = 0              # cold restarts (guards tripped)
        self.warm_extends = 0        # epochs that reused the fixpoint
        self.squarings = 0           # device squaring dispatches, total
        self.oracle_mismatches = 0

    def _check(self, h) -> Dict[str, Any]:
        try:
            return self._incremental_check(h)
        except Exception:  # noqa: BLE001 — device trouble: cold path
            self._state = None
            self.resets += 1
            return super()._check(h)

    def _warm(self, enc: EncodedHistory, edges, n_pad: int) -> bool:
        st = self._state
        if st is None or st.n_pad > n_pad or st.n > enc.n:
            return False
        if not (np.array_equal(st.invoke, enc.invoke[:len(st.invoke)])
                and np.array_equal(st.complete,
                                   enc.complete[:len(st.complete)])):
            return False
        lost = st.edges - edges
        return not lost or _lost_edges_implied(lost, edges)

    def _incremental_check(self, h) -> Dict[str, Any]:
        from jepsen_tpu.elle_tpu.anomalies import finish_lane

        enc = encode(h, self.workload)
        n_pad = pow2_at_least(max(1, enc.n), MIN_N_BUCKET)
        edges = _edge_set(enc)
        warm = self._warm(enc, edges, n_pad)
        if warm and self._state is not None:
            prev_full = _grow(self._state.cl_full, n_pad)
            prev_nonrw = _grow(self._state.cl_nonrw, n_pad)
            prev_g0 = _grow(self._state.cl_g0, n_pad)
            self.warm_extends += 1
        else:
            zero = jnp.zeros((n_pad, n_pad), jnp.float32)
            prev_full = prev_nonrw = prev_g0 = zero
            if self._state is not None:
                self.resets += 1
            self._state = None

        src, dst = _pad_edges(enc)
        m_full, m_nonrw, m_g0, rw = _seed_fn(n_pad, self.realtime)(
            jnp.asarray(src), jnp.asarray(dst),
            jnp.asarray(enc.invoke), jnp.asarray(enc.complete),
            prev_full, prev_nonrw, prev_g0)

        sq = _square_fn(n_pad)
        sums_prev = None
        for _ in range(max(1, math.ceil(math.log2(n_pad))) + 2):
            m_full, m_nonrw, m_g0, sums = sq(m_full, m_nonrw, m_g0)
            self.squarings += 1
            s = np.asarray(sums)
            if sums_prev is not None and np.array_equal(s, sums_prev):
                break
            sums_prev = s

        flags = np.asarray(_flags_fn(n_pad)(m_full, m_nonrw, m_g0, rw))

        if oracle_enabled():
            cold_flags, _, _ = lane_flags_fn(n_pad, self.realtime)(
                jnp.asarray(src)[None], jnp.asarray(dst)[None],
                jnp.asarray(enc.invoke[None]),
                jnp.asarray(enc.complete[None]))
            cold = np.asarray(cold_flags)[0]
            if not np.array_equal(flags.astype(bool), cold.astype(bool)):
                self.oracle_mismatches += 1
                flags = cold    # the cold kernel wins — it IS the oracle

        self._state = _ClosureState(
            n=enc.n, n_pad=n_pad, edges=edges,
            invoke=enc.invoke.copy(), complete=enc.complete.copy(),
            cl_full=m_full, cl_nonrw=m_nonrw, cl_g0=m_g0)

        models = (("strict-serializable",) if self.realtime
                  else ("serializable",))
        deadline = Deadline.after(self.budget_s)
        res = finish_lane(enc, flags, self.realtime, models,
                          budget=deadline.search_budget())
        res["analyzer"] = "elle-stream"
        return res

    def counters(self) -> Dict[str, int]:
        c = super().counters()
        c["elle-resets"] = self.resets
        c["elle-warm-extends"] = self.warm_extends
        c["elle-squarings"] = self.squarings
        if oracle_enabled():
            c["elle-oracle-mismatches"] = self.oracle_mismatches
        return c
