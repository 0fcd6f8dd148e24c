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
  close paths of any length <= N.  ``Graph.add_edge`` never stores
  self-edges, so a nonzero closure diagonal is a genuine cycle.
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

#: closures a lane runs (full, nonrw, g0) and adjacency layers it builds by
#: a one-hot product (ww, wr, rw; the realtime layer is a comparison)
CLOSURES_PER_LANE = 3
LAYER_BUILDS_PER_LANE = 3


def closure_rounds(n_pad: int) -> int:
    """Squarings one closure runs at this size: every one of them, there
    is no early exit."""
    return max(1, math.ceil(math.log2(n_pad)))


def transitive_closure(adj: jnp.ndarray, n_iters: int) -> jnp.ndarray:
    """Close a 0/1 float adjacency matrix over paths of length >= 1."""
    def body(_, r):
        return jnp.minimum(r + r @ r, 1.0)
    return jax.lax.fori_loop(0, n_iters, body, adj)


def _layer(src: jnp.ndarray, dst: jnp.ndarray, n: int) -> jnp.ndarray:
    """[E]-indexed edge endpoints -> [N, N] 0/1 adjacency, by matmul."""
    oh_s = jax.nn.one_hot(src, n, dtype=jnp.float32)   # [E, N]; -1 -> 0s
    oh_d = jax.nn.one_hot(dst, n, dtype=jnp.float32)
    return jnp.minimum(oh_s.T @ oh_d, 1.0)


@lru_cache(maxsize=None)
def lane_flags_fn(n_pad: int, realtime: bool):
    """The jitted vmapped kernel for one (n_pad, realtime) shape class.

    Takes ``src/dst [B, 3, E]`` and ``invoke/complete [B, N]``; returns
    ``[B, len(FLAG_NAMES)]`` bools.  Edge-count ``E`` may vary between
    calls (jit retraces per shape; e_pad is quantized to multiples of 64
    by graphs.pack_group to bound the variant count)."""
    n_iters = closure_rounds(n_pad)

    def lane(src, dst, invoke, complete):
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
        with jax.named_scope("elle.closure"):
            cl_full = transitive_closure(full, n_iters)
            cl_nonrw = transitive_closure(nonrw, n_iters)
            cl_g0 = transitive_closure(g0_adj, n_iters)
        with jax.named_scope("elle.flags"):
            cyclic = jnp.trace(cl_full) > 0
            g0 = jnp.trace(cl_g0) > 0
            g1c = jnp.trace(cl_nonrw) > 0
            # rw edge a->b plus a nonrw path b ->* a: cl_nonrw[b, a] read
            # through the transpose aligns with rw[a, b].
            g_single = jnp.sum(rw * cl_nonrw.T) > 0
            return jnp.stack([cyclic, g0, g1c, g_single])

    return jax.jit(jax.vmap(lane))
