"""The device kernel: boolean-matmul transitive closure, vmapped flags.

Per lane the kernel rebuilds the dependency graph as stacked ``[N, N]``
float32 0/1 adjacency layers and answers four booleans:

- ``cyclic``    — any cycle in ww ∪ wr ∪ rw ∪ rt (the full graph);
- ``g0``        — any cycle in ww ∪ rt (a pure write cycle);
- ``g1c``       — any cycle in ww ∪ wr ∪ rt (information-flow cycle);
- ``g-single``  — some rw edge a->b with a return path b ->* a through
  non-rw layers: exactly one anti-dependency in the cycle (the same
  predicate elle.graph.gsingle_cycles searches per rw edge).

Construction notes:

- Adjacency layers come from one-hot matmuls (``one_hot(src).T @
  one_hot(dst)``), never scatters: a vmapped scatter into bool arrays
  miscompiles at >= 1024 lanes (parallel/batch.py MAX_LANES_PER_GROUP
  documents the minimized repro), and an int/float matmul is the shape
  TPUs like anyway.  ``-1`` padding one-hots to a zero row and vanishes.
- The realtime layer is a broadcast comparison, not an edge list:
  ``rt[i, j] = (invoke[j] >= 0) & (complete[i] < invoke[j])`` — the CPU
  checker's O(N^2) Python loop (elle.list_append.add_realtime_edges) as
  one fused device op.  Compiled out entirely when ``realtime=False``.
- Closure by repeated squaring: ``R <- min(R + R@R, 1)`` doubles the
  reachable path length per iteration, so ``ceil(log2(N))`` iterations
  close paths of any length <= N: that is the loop's cap, not what it
  runs.  A squaring that sets no new cell left R closed: each closure
  stops there, on the device, after the squarings its own graph needs
  (3 + 1 on the 10,000-transaction append history, whose realtime layer
  is transitive by itself).  The test compares cells (``r_new > r``),
  never a float32 sum, which cannot see one more cell past 2^24.
  ``Graph.add_edge`` never stores self-edges, so a nonzero closure
  diagonal is a genuine cycle.
- float32 0/1 instead of bool: bool matmul lowers poorly and the min()
  re-clamp keeps values exact (0.0/1.0) — no epsilon drift.
"""

from __future__ import annotations

import math
from functools import lru_cache

import jax
import jax.numpy as jnp

#: order of the per-lane flag vector returned by the kernel.
FLAG_NAMES = ("cyclic", "g0", "g1c", "g-single")

#: the ``jax.named_scope`` of each phase of a lane, in the lowered program's
#: locations (metadata: the program itself is the same without them)
KERNEL_SCOPES = ("elle.layers", "elle.realtime", "elle.closure",
                 "elle.flags")

#: closures a lane runs, in the order of the rounds the kernel reports,
#: and adjacency layers it builds by a one-hot product (ww, wr, rw; the
#: realtime layer is a comparison)
CLOSURE_NAMES = ("g0", "nonrw", "full")
CLOSURES_PER_LANE = len(CLOSURE_NAMES)
LAYER_BUILDS_PER_LANE = 3


def closure_rounds(n_pad: int) -> int:
    """The cap on one closure's squarings at this size: after it every
    path of up to ``n_pad`` nodes is closed.  A closure stops before it
    once a squaring changes nothing (:func:`transitive_closure`)."""
    return max(1, math.ceil(math.log2(n_pad)))


def grew(r_new: jnp.ndarray, r: jnp.ndarray) -> jnp.ndarray:
    """Whether a squaring set any cell of each ``[N, N]`` matrix: R only
    grows, so this is ``r_new != r``, exactly (no sum of cells, which in
    float32 cannot see one more past 2^24)."""
    return jnp.any(r_new > r, axis=(-2, -1))


def transitive_closure(adj: jnp.ndarray, cap: int):
    """Close each 0/1 float adjacency matrix of ``adj`` (``[..., N, N]``)
    over paths of length >= 1: ``(closures, squarings [...])``.

    Squares until a squaring changes no matrix, or ``cap`` squarings have
    run.  A matrix that a squaring left unchanged is closed, and squaring
    it again leaves it as it is, so the batch is squared whole, with no
    per-matrix select; each matrix's own count is the squarings up to and
    including the one that left it unchanged (all of them at the cap),
    and the device ran the largest of them for every matrix.  The change
    test fuses into the product's epilogue: no pass of its own, and no
    count taken before the loop, which would hold every layer at once."""
    def cond(c):
        _, active, _, k = c
        return jnp.any(active) & (k < cap)

    def body(c):
        r, active, rounds, k = c
        r_new = jnp.minimum(r + r @ r, 1.0)
        return r_new, grew(r_new, r), rounds + active, k + 1

    batch = adj.shape[:-2]
    r, _, rounds, _ = jax.lax.while_loop(
        cond, body, (adj, jnp.ones(batch, bool), jnp.zeros(batch, jnp.int32),
                     jnp.int32(0)))
    return r, rounds


def _layer(src: jnp.ndarray, dst: jnp.ndarray, n: int) -> jnp.ndarray:
    """[E]-indexed edge endpoints -> [N, N] 0/1 adjacency, by matmul."""
    oh_s = jax.nn.one_hot(src, n, dtype=jnp.float32)   # [E, N]; -1 -> 0s
    oh_d = jax.nn.one_hot(dst, n, dtype=jnp.float32)
    return jnp.minimum(oh_s.T @ oh_d, 1.0)


@lru_cache(maxsize=None)
def lane_flags_fn(n_pad: int, realtime: bool):
    """The jitted vmapped kernel for one (n_pad, realtime) shape class.

    Takes ``src/dst [B, 3, E]`` and ``invoke/complete [B, N]``; returns
    ``flags [B, len(FLAG_NAMES)]`` bools, ``rounds [B, 3]`` int32 (the
    squarings each of a lane's closures needed, ``CLOSURE_NAMES`` order)
    and ``summary [4]`` int32: the flags set over the group, then each
    closure's most rounds over its lanes, which is what the device ran
    for every lane (a closure's loop runs until its last lane stops).  One
    read of ``summary`` tells the host whether any flag is set and what
    the group cost.  Edge-count ``E`` may vary between calls (jit
    retraces per shape; e_pad is quantized to multiples of 64 by
    graphs.pack_group to bound the variant count)."""
    cap = closure_rounds(n_pad)

    def layers(src, dst, invoke, complete):
        with jax.named_scope("elle.layers"):
            ww = _layer(src[0], dst[0], n_pad)
            wr = _layer(src[1], dst[1], n_pad)
            rw = _layer(src[2], dst[2], n_pad)
        with jax.named_scope("elle.realtime"):
            if realtime:
                rt = ((complete[:, None] < invoke[None, :])
                      & (invoke[None, :] >= 0)).astype(jnp.float32)
            else:
                rt = jnp.zeros((n_pad, n_pad), jnp.float32)
            nonrw = jnp.minimum(ww + wr + rt, 1.0)
            full = jnp.minimum(nonrw + rw, 1.0)
            g0_adj = jnp.minimum(ww + rt, 1.0)
        return g0_adj, nonrw, full, rw

    def flags(cl_g0, cl_nonrw, cl_full, rw):
        with jax.named_scope("elle.flags"):
            cyclic = jnp.trace(cl_full) > 0
            g0 = jnp.trace(cl_g0) > 0
            g1c = jnp.trace(cl_nonrw) > 0
            # rw edge a->b plus a nonrw path b ->* a: cl_nonrw[b, a] read
            # through the transpose aligns with rw[a, b].
            g_single = jnp.sum(rw * cl_nonrw.T) > 0
            return jnp.stack([cyclic, g0, g1c, g_single])

    def group(src, dst, invoke, complete):
        g0_adj, nonrw, full, rw = jax.vmap(layers)(src, dst, invoke,
                                                   complete)
        # three loops over the group's lanes, each stopping on its own
        with jax.named_scope("elle.closure"):
            cl_g0, k_g0 = transitive_closure(g0_adj, cap)
            cl_nonrw, k_nonrw = transitive_closure(nonrw, cap)
            cl_full, k_full = transitive_closure(full, cap)
        out = jax.vmap(flags)(cl_g0, cl_nonrw, cl_full, rw)
        rounds = jnp.stack([k_g0, k_nonrw, k_full], axis=1)
        summary = jnp.concatenate([jnp.sum(out, dtype=jnp.int32)[None],
                                   jnp.max(rounds, axis=0)])
        return out, rounds, summary

    return jax.jit(group)
