#!/usr/bin/env python
"""Candidate compaction on the chip: the old C*W-row stable sort against
each form of rank and select that ships, at the shapes the benchmark's
cells run.

One closure round's compaction, from the expansion grid's validity to the
NC candidate rows: ``sort`` builds every cell's row and calls
``ops.dedup.compact_rows`` on the flattened grid (what ``compact_to`` did
before PR 28); ``matmul`` and ``blocks`` are
``checker.wgl_tpu.compact_candidates`` in the two forms of
``ops.dedup.compact_grid``.  Each is jitted, vmapped where the cell's
engine is, and looped ``iters`` times inside ONE program (a dispatch costs
more than a round), on inputs that change with the loop index so nothing
hoists; the time is the best of three calls over ``iters``.  All forms of a
shape must return the same checksum.

    chiprun --chips 1 --timeout 1500 -- python scripts/compact_bench.py

A tool, not a cell: nothing under ``benchmark/`` reads it.  The table goes
to stdout and to ``chiprun_out/compact_bench.json``; PERF.md section 6
quotes it with the form ``compaction_form`` picks per shape.
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

from jepsen_tpu.checker import wgl_tpu  # noqa: E402
from jepsen_tpu.models import get_model  # noqa: E402
from jepsen_tpu.ops.dedup import compact_rows  # noqa: E402

FORMS = ("sort", "matmul", "blocks")

#: (C, W, NC, lanes, iters, share of rows that expand).  Lanes 0 = the
#: single-history engine (crash: window 60; clean: 12; rungs 1,024 and
#: 4,096; NC = C/2, C, 4C); lanes > 0 = check_batch's vmapped engine, NC = C
#: (keyed200: window 12, passes of 512 / 6 lanes; nemesis: 16, passes of
#: 256 / 167 / 6 lanes).
SHAPES = (
    (4096, 60, 2048, 0, 200, 0.02),
    (4096, 60, 4096, 0, 200, 0.04),
    (4096, 60, 16384, 0, 100, 0.15),
    (1024, 60, 512, 0, 200, 0.02),
    (4096, 12, 2048, 0, 200, 0.2),
    (1024, 12, 512, 0, 200, 0.2),
    (256, 12, 256, 512, 50, 0.2),
    (2048, 12, 2048, 6, 50, 0.2),
    (256, 16, 256, 256, 50, 0.1),
    (2048, 16, 2048, 167, 20, 0.1),
    (16384, 16, 16384, 6, 20, 0.1),
)


def sort_compaction(step, mask, states, win_ops, cv, NC):
    """The compaction before PR 28: every cell's row, one stable sort."""
    C, W = cv.shape
    slot_masks = np.zeros((W, mask.shape[1]), np.uint32)
    for w in range(W):
        slot_masks[w, w // 32] = np.uint32(1) << np.uint32(w % 32)
    cand_mask = mask[:, None, :] | jnp.asarray(slot_masks)[None]
    cand_states = jax.vmap(lambda st: jax.vmap(
        lambda op: step(st, op[0], op[1], op[2])[0].astype(jnp.int32)
    )(win_ops))(states)
    (cm, cs), valid, total = compact_rows(
        [cand_mask.reshape(C * W, -1), cand_states.reshape(C * W, -1)],
        cv.reshape(C * W), NC)
    return cm, cs, valid, total


def one_round(step, form, NC):
    """mask, states, win_ops, row gate -> compacted candidates, as the
    closure's body does it: expand for ``ok``, gate, compact."""
    def f(mask, states, win_ops, row_gate):
        W = win_ops.shape[0]
        ok = jax.vmap(lambda st: jax.vmap(
            lambda op: step(st, op[0], op[1], op[2])[1])(win_ops))(states)
        slot = jnp.arange(W)
        has = (jnp.take(mask, slot // 32, axis=1)
               >> (slot % 32).astype(jnp.uint32)[None, :]) & 1
        cv = row_gate[:, None] & (has == 0) & ok
        if form == "sort":
            return sort_compaction(step, mask, states, win_ops, cv, NC)
        return wgl_tpu.compact_candidates(step, mask, states, win_ops, cv,
                                          NC, form)
    return f


def inputs(model, C, W, lanes, share, seed=1):
    rng = np.random.default_rng(seed)
    MW = (W + 31) // 32
    lead = (lanes,) if lanes else ()
    mask = rng.integers(0, 2**32, lead + (C, MW), dtype=np.uint32)
    mask &= rng.integers(0, 2**32, lead + (C, MW), dtype=np.uint32)
    if W % 32:
        mask[..., -1] &= np.uint32((1 << (W % 32)) - 1)
    states = rng.integers(0, 5, lead + (C, model.state_size)).astype(
        np.int32)
    win_ops = np.zeros(lead + (W, 6), np.int32)
    win_ops[..., 0] = rng.integers(0, 3, lead + (W,))
    win_ops[..., 1:3] = rng.integers(0, 5, lead + (W, 2))
    gate = rng.random(lead + (C,)) < share
    return tuple(jnp.asarray(x) for x in (mask, states, win_ops, gate))


def bench(model, C, W, NC, lanes, form, iters, share):
    f = one_round(model.step, form, NC)
    f = jax.vmap(f) if lanes else f

    def prog(mask, states, win_ops, gate):
        def body(i, acc):
            cm, cs, valid, total = f(mask, states + (i & 1), win_ops,
                                     jnp.roll(gate, i, axis=-1))
            chk = (cm.sum(dtype=jnp.uint32).astype(jnp.int32) + cs.sum()
                   + valid.sum())
            return acc[0] + chk, acc[1] + jnp.sum(total)
        return lax.fori_loop(0, iters, body, (jnp.int32(0), jnp.int32(0)))

    args = inputs(model, C, W, lanes, share)
    run = jax.jit(prog)
    t0 = time.perf_counter()
    out = jax.block_until_ready(run(*args))
    compile_s = time.perf_counter() - t0
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = jax.block_until_ready(run(*args))
        best = min(best, time.perf_counter() - t0)
    return {"C": C, "W": W, "NC": NC, "lanes": lanes, "form": form,
            "us_per_round": best / iters * 1e6,
            "first_call_s": compile_s,
            "candidates_per_round": int(out[1]) / iters / max(1, lanes),
            "checksum": int(out[0])}


def main():
    dev = jax.devices()[0]
    print(f"# device {dev.platform} {dev.device_kind}", flush=True)
    model = get_model("cas-register")
    rows = []
    for C, W, NC, lanes, iters, share in SHAPES:
        shape_rows = [bench(model, C, W, NC, lanes, form, iters, share)
                      for form in FORMS]
        assert len({r["checksum"] for r in shape_rows}) == 1, shape_rows
        chosen = wgl_tpu.compaction_form(C)
        for r in shape_rows:
            r["built"] = r["form"] == chosen
            print(json.dumps(r), flush=True)
        rows += shape_rows
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/compact_bench.json", "w") as fh:
        json.dump({"device": [dev.platform, dev.device_kind], "rows": rows},
                  fh, indent=1)


if __name__ == "__main__":
    main()
