#!/usr/bin/env python
"""The subsumption probe's head fetch on the chip: the head-index sort and
its gathers (what ``ops.dedup.sort_dedup_compact`` did before PR 30)
against each form of the segmented copy scan, at the shapes the
benchmark's cells run.

Every form is a ``head_words(is_head, cols)``: for each row, each
column's value at the nearest head row at or before it.  ``gather`` is the
old one (a stable sort ranks the head rows' indices first, one gather
finds each row's head, one more a column fetches its word); ``roll`` is
``ops.dedup.head_words``, doubling by static rolls; ``assoc`` is
``lax.associative_scan`` over (flag, value) pairs.  (A two-level scan in
rows of 128 was measured and removed: PERF.md, PR 30.)  Two programs a
form and shape: ``probe`` is the head probe
alone, from ``is_head`` to the hit mask; ``merge`` is the whole
``sort_dedup_compact`` with the form in ``head_words``' place.  Each is
jitted, vmapped where the cell's engine is, and looped ``iters`` times
inside ONE program on inputs that change with the loop index, so nothing
hoists; the time is the best of three calls over ``iters``.  All forms of
a shape must return the same checksum.

    chiprun --chips 1 --timeout 1500 -- python scripts/probe_bench.py \
        [probe,merge,largest [gather,roll,assoc]]

``largest`` is ``merge`` at the largest merge the ladder reaches (capacity
65,536's tiled full-grid fold keeps C + tile under ``WIDE_SORT_ROWS``): it
shows the scan compile and run inside a loop at a million rows, where
``lax.cummax`` once crashed the TPU's compiler (``largest roll`` is enough
for that; a form costs some nine minutes of compiling there).

A tool, not a cell: nothing under ``benchmark/`` reads it.  The table goes
to stdout and to ``chiprun_out/probe_bench.json``; PERF.md section 6
quotes it.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from jepsen_tpu.ops import dedup  # noqa: E402

#: (rows, ghost words, lanes, iters).  Lanes 0 = the single-history engine
#: (crash: C + NC = 1,024 + 512 and 4,096 + 2,048, 2 ghost words; clean: 1);
#: lanes > 0 = check_batch's vmapped engine, C + NC = 2C, 1 ghost word
#: (keyed200: passes of 512 / 6 lanes; nemesis: 256 / 167 / 6 lanes).
SHAPES = (
    (1536, 1, 0, 200),
    (1536, 2, 0, 200),
    (6144, 1, 0, 200),
    (6144, 2, 0, 200),
    (512, 1, 512, 50),
    (512, 1, 256, 50),
    (4096, 1, 167, 20),
    (32768, 1, 6, 20),
)
LARGEST = (65536 * 18, 2, 0, 3)


def gather_form(is_head, cols):
    """The head fetch before PR 30: sort, take, gather."""
    n = is_head.shape[0]
    seg = jnp.cumsum(is_head.astype(jnp.int32)) - 1
    _, head_idx = lax.sort(((~is_head).astype(jnp.int32),
                            jnp.arange(n, dtype=jnp.int32)),
                           num_keys=1, is_stable=True)
    head_of = jnp.take(head_idx, jnp.clip(seg, 0, n - 1))
    return [c[jnp.maximum(head_of, 0)] for c in cols]


def assoc_form(is_head, cols):
    def later(a, b):
        return (a[0] | b[0],
                *[jnp.where(b[0], y, x) for x, y in zip(a[1:], b[1:])])
    return list(lax.associative_scan(later, (is_head, *cols))[1:])


FORMS = {"gather": gather_form, "roll": dedup.head_words,
         "assoc": assoc_form}


def probe(form):
    """is_head, s_valid, ghost columns -> the head probe's hit mask and the
    head words it compared, as sort_dedup_compact's block has them."""
    def f(is_head, s_valid, *cols):
        in_group = s_valid & ~is_head
        hit, words = in_group, []
        for c, head_c in zip(cols, FORMS[form](is_head, cols)):
            hit &= (head_c & ~c) == 0
            words.append(jnp.where(in_group, head_c, 0))
        return hit, words
    return f


def merge(form, G, capacity):
    """The whole dedup of one merge, ``form`` in head_words' place."""
    def f(valid, origin, *cols):
        real, dedup.head_words = dedup.head_words, FORMS[form]
        try:
            return dedup.sort_dedup_compact(
                cols[:-G], valid, capacity, ghost_cols=cols[-G:],
                origin=origin)
        finally:
            dedup.head_words = real
    return f


def checksum(tree):
    return sum(x.astype(jnp.uint32).sum(dtype=jnp.uint32)
               for x in jax.tree_util.tree_leaves(tree))


def probe_inputs(n, G, lanes, seed=1):
    rng = np.random.default_rng(seed)
    lead = (lanes,) if lanes else ()
    n_valid = rng.integers(n // 2, n + 1, lead + (1,))
    s_valid = np.arange(n) < n_valid
    is_head = (rng.random(lead + (n,)) < 0.4) & s_valid
    is_head[..., 0] = s_valid[..., 0]
    cols = [rng.integers(0, 16, lead + (n,), dtype=np.uint32)
            for _ in range(G)]
    return tuple(jnp.asarray(x) for x in (is_head, s_valid, *cols))


def merge_inputs(n, G, lanes, seed=1):
    # G words of mask and one state: the engines' key columns; few values,
    # so that groups form
    rng = np.random.default_rng(seed)
    lead = (lanes,) if lanes else ()
    valid = rng.random(lead + (n,)) < 0.8
    origin = (rng.random(lead + (n,)) < 0.5).astype(np.int32)
    keys = [rng.integers(0, 4, lead + (n,), dtype=np.uint32)
            for _ in range(G)]
    state = rng.integers(0, 5, lead + (n,)).astype(np.int32)
    ghosts = [rng.integers(0, 16, lead + (n,), dtype=np.uint32)
              for _ in range(G)]
    return tuple(jnp.asarray(x)
                 for x in (valid, origin, *keys, state, *ghosts))


def timed(f, args, lanes, iters, vary):
    """``f`` looped ``iters`` times in one program; ``vary(i, args)`` makes
    the round's inputs."""
    g = jax.vmap(f) if lanes else f

    def prog(*args):
        def body(i, acc):
            return acc + checksum(g(*vary(i, args)))
        return lax.fori_loop(0, iters, body, jnp.uint32(0))

    run = jax.jit(prog)
    t0 = time.perf_counter()
    out = jax.block_until_ready(run(*args))
    first = time.perf_counter() - t0
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = jax.block_until_ready(run(*args))
        best = min(best, time.perf_counter() - t0)
    return {"us_per_round": best / iters * 1e6, "first_call_s": first,
            "checksum": int(out)}


def vary_probe(i, args):
    is_head, s_valid, *cols = args
    n = is_head.shape[-1]
    idx = jnp.arange(n)
    extra = ((idx + i) % 61 == 0) | (idx == 0)
    return (s_valid & (is_head | extra), s_valid,
            *[c ^ (i & 3).astype(jnp.uint32) for c in cols])


def vary_merge(i, args):
    valid, origin, *cols = args
    return (jnp.roll(valid, i, axis=-1), origin,
            *[c ^ (i & 1).astype(c.dtype) for c in cols])


def bench(n, G, lanes, iters, form, what):
    if what == "probe":
        r = timed(probe(form), probe_inputs(n, G, lanes), lanes, iters,
                  vary_probe)
    else:
        r = timed(merge(form, G, n // 2), merge_inputs(n, G, lanes), lanes,
                  iters, vary_merge)
    return {"rows": n, "ghost_words": G, "lanes": lanes, "what": what,
            "form": form, **r}


def main():
    dev = jax.devices()[0]
    print(f"# device {dev.platform} {dev.device_kind}", flush=True)
    whats = (sys.argv[1] if len(sys.argv) > 1 else "probe,merge").split(",")
    forms = sys.argv[2].split(",") if len(sys.argv) > 2 else list(FORMS)
    todo = [(shape, what) for shape in SHAPES for what in whats
            if what != "largest"]
    if "largest" in whats:
        todo.append((LARGEST, "merge"))
    os.makedirs("chiprun_out", exist_ok=True)
    rows = []
    for (n, G, lanes, iters), what in todo:
        shape_rows = [bench(n, G, lanes, iters, form, what)
                      for form in forms]
        assert len({r["checksum"] for r in shape_rows}) == 1, shape_rows
        for r in shape_rows:
            r["built"] = r["form"] == "roll"
            print(json.dumps(r), flush=True)
        rows += shape_rows
        with open("chiprun_out/probe_bench.json", "w") as fh:
            json.dump({"device": [dev.platform, dev.device_kind],
                       "rows": rows}, fh, indent=1)


if __name__ == "__main__":
    main()
