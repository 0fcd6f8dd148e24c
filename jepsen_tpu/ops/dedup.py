"""Row deduplication and compaction in fixed-capacity buffers.

The TPU search's configuration sets live in fixed-shape buffers; after each
closure expansion the union of (existing ∪ candidate) rows must be
deduplicated and compacted back to capacity, with static shapes, no host
round-trips and no dynamic allocation.  Three pieces:

- :func:`sort_dedup_compact`: rows are fully described by their key
  columns, so a multi-operand lexicographic ``lax.sort`` (invalid rows keyed
  last), a neighbour-equality pass and a compaction do the dedup.  This
  replaces what knossos does with JVM hash sets of configuration objects.
- :func:`compact_rows`: the compaction of an arbitrary bag of rows, one
  stable sort keyed on ``~keep`` with the columns as payload.  Its cost is
  the sort's, whatever the number kept: right for the dedup's own C + NC
  rows, where most are kept.
- :func:`compact_grid`: the compaction of a [C, W] grid whose cells are
  functions of (row, slot), by rank and select: a running count by row
  says which row and which of its set cells each output is, and nothing
  but the NC outputs is ever moved.  The closure's candidate compaction
  (``checker.wgl_tpu.compact_candidates``) keeps a few hundred to a few
  thousand of C*W cells, and did it with :func:`compact_rows` until PR 28:
  one 245,760-row sort a round, 58% of the 10k-op crash cell's verdict.

What a TPU v5e charges (``scripts/compact_bench.py``,
``scripts/probe_bench.py`` and the traces of the benchmark's cells;
PERF.md, PR 28 and PR 30).  The old candidate compaction at C 4,096 x W
60, grid and sort: 0.52 ms a round; rank and select 0.03 ms.  A gather
runs one element after another, 5 to 8 ns each (a binary search of 12
rounds over 2,048 outputs: 0.19 ms; one 6,144-element gather: 0.04 ms;
262,144 elements, 512 rows x 512 lanes: 1.8 ms), so a gather is cheap only
where its OUTPUT is small, and a search by gathers never is.  A [NC, C]
one-hot times a narrow table on the MXU, the one-hot made inside the dot's
fusion, beats every form that gathers up to C 8,192.  A static roll and a
select over whole vectors are the cheap things: one step of
:func:`head_words`' scan is 0.75 us at 6,144 rows x 2 ghost words (13
steps, 9.8 us; the sort and three gathers it replaced: 147.5 us) and 3.2 us
at 512 rows x 512 lanes (9 steps, 29.0 us against 5,420 us).  Scatters were
measured slower than sorts by the sessions before the benchmark and have
not been measured since: nothing here scatters, and since PR 30 nothing in
:func:`sort_dedup_compact` under ``WIDE_SORT_ROWS`` gathers.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: Subsumption probe count (earlier in-group rows checked per row).  A
#: constant of the program: engines embed it in their cache keys (see
#: wgl_tpu.make_engine), and the tests that need another value patch it
#: here.  3 (was 5): measured on hardware, probes 3 drop exactly the same
#: rows on the crash-heavy hard tier (same configs explored, same capacity
#: trajectory) while the per-merge gather/compare chains cost ~9% of the
#: easy-tier wall (7.5s -> 6.9s).
N_PROBES = 3

#: Above this row count the dedup sorts with ``_lex_perm`` (a chain of
#: 2-operand stable sorts composing a permutation) instead of one wide
#: variadic ``lax.sort``.  A 7-operand sort over C*(W+1) ~ 4.26M rows
#: (capacity 65536 x window 64, the bench hard tier) crashes the TPU worker
#: outright; 2-operand sorts at the same row count compile in ~26 s and run
#: in milliseconds.  1.06M-row x 7-operand variadic sorts are measured-good,
#: so the threshold keeps the single-sort path for every small shape.
WIDE_SORT_ROWS = 1_200_000

#: Ghost subsumption (the subset-drop).  With it off, ghost columns act as
#: plain identity columns, i.e. the classic 2^crashes configuration search:
#: same verdicts, more configurations.  Part of the engine cache key; the
#: tests patch it to False to hold the two searches against each other.
SUBSUME = True


def compact_rows(cols: Sequence[jnp.ndarray], keep: jnp.ndarray,
                 capacity: int):
    """Stable compaction of the rows where ``keep`` into ``capacity``-row
    buffers via one stable sort (no scatter).

    A single stable sort keyed on ``~keep`` with every column riding as a
    payload operand puts the kept rows first, in order: payloads do not
    enter the comparator (``num_keys=1``), the permutation network carries
    them.  The cost is that of sorting all ``n`` rows, however few are
    kept, so this is for bags of rows of which most are kept
    (:func:`sort_dedup_compact`'s final step); a grid of which few are,
    and whose rows can be recomputed from their position, goes through
    :func:`compact_grid`.  Rows past the kept count are zeroed (callers
    gate on ``valid``, but zeroed tails keep artifacts reproducible).
    Rows past ``capacity`` are silently truncated — callers detect that
    via ``total``.

    Returns ``(out_cols, out_valid, total)``.
    """
    n = keep.shape[0]
    total = jnp.sum(keep.astype(jnp.int32))
    flat, meta = [], []
    for c in cols:
        if c.ndim == 1:
            flat.append(c)
            meta.append(None)
        else:
            flat.extend(c[:, j] for j in range(c.shape[1]))
            meta.append(c.shape[1])
    if n <= WIDE_SORT_ROWS:
        sorted_ops = jax.lax.sort(
            tuple([(~keep).astype(jnp.int32)] + flat),
            num_keys=1, is_stable=True)[1:]
    else:
        # Multi-million-row wide variadic sorts crash the TPU compiler
        # (see WIDE_SORT_ROWS): sort only (key, iota) and gather each
        # column — gather cost scales with the OUTPUT (capacity), not n.
        _, src = jax.lax.sort(((~keep).astype(jnp.int32),
                               jnp.arange(n, dtype=jnp.int32)),
                              num_keys=1, is_stable=True)
        src = src[:min(capacity, n)]
        sorted_ops = [jnp.take(c, src, axis=0) for c in flat]
        n = src.shape[0]
    out_valid = jnp.arange(capacity) < total

    def fit(c):
        c = c[:capacity] if capacity <= n else jnp.concatenate(
            [c, jnp.zeros(capacity - n, c.dtype)])
        return jnp.where(out_valid, c, jnp.zeros((), c.dtype))

    outs, k = [], 0
    for m in meta:
        if m is None:
            outs.append(fit(sorted_ops[k]))
            k += 1
        else:
            outs.append(jnp.stack([fit(sorted_ops[k + j])
                                   for j in range(m)], axis=-1))
            k += m
    return outs, out_valid, total


def compact_grid(cv: jnp.ndarray, row_cols: Sequence[jnp.ndarray],
                 capacity: int, form: str = "matmul"):
    """The first ``capacity`` set cells of the bool grid ``cv`` ([C, W]), in
    row-major order, by rank and select: for each of them its row's entry
    of every column in ``row_cols`` (each [C, n], 32-bit) and its
    slot.  The same cells in the same order as :func:`compact_rows` on the
    flattened grid, ``valid`` and ``total`` alike, without moving the C*W
    cells: where a cell's columns are functions of (row, slot), finding
    the pair is enough.

    1. ``incl`` = running count of set cells by row (C elements), ``excl``
       the same less the row's own: output ``j`` is the cell of rank
       ``j - excl[r]`` in the one row ``r`` with ``excl[r] <= j < incl[r]``.
    2. A table row holds the row's cells packed into bit words, its
       ``excl`` and its ``row_cols``; each output fetches its row's entry:

       - ``"matmul"``: the one-hot [capacity, C] of step 1 times the
         table's bytes on the MXU (exact: 0/1 and 0..255 are exact in
         bf16, one product is non-zero, float32 accumulates), the one-hot
         made inside the dot's fusion and never stored.  No search, no
         gather; C x capacity work, the cheapest form up to C 8,192;
       - ``"blocks"``: ``r`` by a two-level count (which block of about
         sqrt(C) rows, then which row inside the gathered block), then one
         row gather of the table.  Two gathers of ``capacity`` rows (about
         7 ns a row on a v5e) and no C x capacity term, for large C.
    3. The slot is the position of the row's (k+1)-th set cell, counted
       with popcounts over [capacity, W].

    Returns ``(out_cols, slot, valid, total)``.  ``valid[j] = j < total``;
    rows past ``total`` hold whatever the fetch found there and are the
    caller's to zero; cells past ``capacity`` are dropped silently, which
    callers detect through ``total``.
    """
    C, W = cv.shape
    n_words = (W + 31) // 32
    cnt = cv.sum(1, dtype=jnp.int32)
    incl = jnp.cumsum(cnt)
    excl = incl - cnt
    total = incl[-1]
    j = jnp.arange(capacity, dtype=jnp.int32)
    bit_of = np.uint32(1) << (np.arange(W) % 32).astype(np.uint32)
    words = [(cv[:, lo:lo + 32] * jnp.asarray(bit_of[lo:lo + 32])[None, :])
             .sum(1, dtype=jnp.uint32) for lo in range(0, W, 32)]

    def as_words(c):
        return jax.lax.bitcast_convert_type(c, jnp.uint32)

    table = jnp.concatenate([jnp.stack(words, -1), as_words(excl[:, None])]
                            + [as_words(c) for c in row_cols], axis=1)
    K = table.shape[1]
    if form == "matmul":
        onehot = (excl[None, :] <= j[:, None]) & (j[:, None] < incl[None, :])
        table_bytes = jnp.stack([(table >> (8 * i)) & 0xFF
                                 for i in range(4)], -1).reshape(C, 4 * K)
        got = jnp.dot(onehot.astype(jnp.bfloat16),
                      table_bytes.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
        got = got.astype(jnp.uint32).reshape(capacity, K, 4)
        got = (got[..., 0] | (got[..., 1] << 8) | (got[..., 2] << 16)
               | (got[..., 3] << 24))
    else:
        B = 1 << ((C - 1).bit_length() + 1) // 2
        n_blocks = -(-C // B)
        # padding reads ``total``: never <= a j that is valid
        blocks = jnp.concatenate(
            [incl, jnp.full(n_blocks * B - C, total)]).reshape(n_blocks, B)
        blk = jnp.minimum(
            (blocks[None, :, -1] <= j[:, None]).sum(1, dtype=jnp.int32),
            n_blocks - 1)
        row = blk * B + (jnp.take(blocks, blk, axis=0)
                         <= j[:, None]).sum(1, dtype=jnp.int32)
        got = jnp.take(table, jnp.minimum(row, C - 1), axis=0)
    k = j - got[:, n_words].astype(jnp.int32)
    # below[j, p] = set cells of the row at positions <= p
    below = 0
    for i in range(n_words):
        upto = np.where(np.arange(W) // 32 > i, np.uint32(0xFFFFFFFF),
                        np.where(np.arange(W) // 32 == i,
                                 (bit_of << np.uint32(1)) - np.uint32(1),
                                 np.uint32(0))).astype(np.uint32)
        below = below + jax.lax.population_count(
            got[:, i, None] & jnp.asarray(upto)[None, :]).astype(jnp.int32)
    slot = jnp.minimum((below <= k[:, None]).sum(1, dtype=jnp.int32), W - 1)
    outs, at = [], n_words + 1
    for c in row_cols:
        outs.append(jax.lax.bitcast_convert_type(
            got[:, at:at + c.shape[1]], c.dtype))
        at += c.shape[1]
    return outs, slot, j < total, total


def _lex_perm(keys: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Permutation sorting rows lexicographically by ``keys`` (first key most
    significant), stable — equivalent to ``np.lexsort(reversed(keys))``.

    Built least-significant-key-first from 2-operand stable sorts: each pass
    gathers the next key through the permutation so far and stable-sorts
    (key, perm).  Stability makes the passes compose into a lexicographic
    order.  Narrow sorts sidestep the TPU compiler failure that wide variadic
    sorts hit at multi-million-row shapes."""
    n = keys[0].shape[0]
    perm = jnp.arange(n, dtype=jnp.int32)
    for k in reversed(list(keys)):
        kk = jnp.take(k, perm)
        _, perm = jax.lax.sort((kk, perm), num_keys=1, is_stable=True)
    return perm


def head_words(is_head: jnp.ndarray,
               cols: Sequence[jnp.ndarray]) -> List[jnp.ndarray]:
    """For each row and each column of ``cols`` (all [n]), the column's
    value at the nearest ``is_head`` row at or before the row: a segmented
    copy scan by doubling, ``ceil(log2 n)`` steps of static rolls and
    selects on whole vectors.

    After the step of distance ``d``, ``have`` says that a head lies within
    ``2d - 1`` rows behind, and the value is that head's.  The rolls wrap,
    and that is harmless wherever row 0 is a head: row ``i`` has its head
    by the time ``d`` passes ``i``, and takes nothing from the far end.
    A row with no head at or before it gets an arbitrary row's value; the
    caller masks those (only invalid rows are such).

    No ``reduce_window`` (``lax.cummax`` inside a ``while_loop`` crashed the
    TPU's compiler at about a million rows) and no strided slice
    (``lax.associative_scan`` costs 1.2 to 8 times as much on a v5e:
    ``scripts/probe_bench.py``).  At the largest merge the ladder reaches
    (1,179,648 rows, 2 ghost words, 21 steps, inside a loop) the whole
    dedup compiled on the chip in 237 s and ran in 11.2 ms a round.
    """
    n = is_head.shape[0]
    have, vals, d = is_head, list(cols), 1
    while d < n:
        vals = [jnp.where(have, v, jnp.roll(v, d)) for v in vals]
        have = have | jnp.roll(have, d)
        d *= 2
    return vals


def sort_dedup_compact(cols: Sequence[jnp.ndarray],
                       valid: jnp.ndarray,
                       capacity: int,
                       ghost_cols: Sequence[jnp.ndarray] = (),
                       origin: jnp.ndarray = None,
                       ):
    """Deduplicate rows described by ``cols`` (+ ``ghost_cols``, each [N],
    int dtypes) among entries where ``valid`` is True; compact the distinct
    rows into buffers of ``capacity`` rows.

    ``ghost_cols`` enable *subsumption*: rows agreeing on every ``cols``
    entry form a group, and a member is dropped when the group's head (the
    sort-first member) has a ghost bitset that is a subset of the member's
    (checked word-wise: ``head & ~row == 0``).  Soundness (see
    checker/wgl_tpu.py): ghost bits mark pending ops that never return, so
    they are never consulted by pruning; a config whose ghost set contains
    the head's is reachable from the head again at any later closure, and
    the head has a superset of its futures.  Without ``ghost_cols`` this is
    plain exact dedup.

    ``origin`` (optional, int32 [N], 1 = newly-generated candidate) is
    carried as a payload; when given, the return gains ``new_rows`` (True
    iff any *kept* row is a candidate — this, not a count delta, is the
    sound fixpoint signal for a closure loop, because subsumption can drop
    existing rows in the same round that adds new ones, leaving the count
    unchanged while the set moved) and ``out_origin``, the compacted
    per-row origin column (the delta closure's next-round expansion
    frontier).  For a dropped duplicate the kept copy's origin wins (the
    stable sort keeps the existing row ahead of an identical candidate).

    Returns ``(out_cols, out_valid, total, overflow[, new_rows,
    out_origin])`` — ``out_cols`` in the order ``[*cols, *ghost_cols]``;
    ``total`` is the number of kept rows (may exceed capacity — then
    ``overflow`` is True and the surplus rows were dropped).
    """
    n = valid.shape[0]
    n_key = len(cols)
    # Key 0: invalid rows sort after all valid rows.  Ghost columns sort
    # ascending after the group key, so a numerically-minimal ghost set
    # (e.g. the empty set) heads its group.  The stable sort keeps an
    # existing row ahead of an identical candidate, so exact-dup keeps the
    # existing one and ``new_rows`` stays quiet.
    inv = (~valid).astype(jnp.int32)
    keys = [inv] + list(cols) + list(ghost_cols)
    extras = [origin] if origin is not None else []
    if n <= WIDE_SORT_ROWS:
        sorted_ops = jax.lax.sort(tuple(keys + extras),
                                  num_keys=1 + n_key + len(ghost_cols))
    else:
        perm = _lex_perm(keys)
        sorted_ops = [jnp.take(c, perm) for c in keys + extras]
    s_inv = sorted_ops[0]
    s_cols = list(sorted_ops[1:1 + n_key])
    s_ghost = list(sorted_ops[1 + n_key:1 + n_key + len(ghost_cols)])
    s_origin = sorted_ops[-1] if origin is not None else None
    s_valid = s_inv == 0

    same_as_prev = jnp.ones(n, dtype=bool)
    for c in s_cols:
        same_as_prev &= c == jnp.roll(c, 1)
    same_as_prev = same_as_prev.at[0].set(False)
    exact_same = same_as_prev
    for c in s_ghost:
        exact_same &= c == jnp.roll(c, 1)
    drop = exact_same & jnp.roll(s_valid, 1)

    if s_ghost and SUBSUME:
        # A group's head is its first row.  The rows are sorted by group,
        # so a row's head is the nearest head at or before it, and its
        # ghost words come down the sorted order (head_words); a gather
        # would fetch them one row after another, 5-8 ns each.  A valid
        # row is behind its head exactly when it is not one (row 0 heads
        # the first group whenever any row is valid).
        is_head = s_valid & ~(same_as_prev & jnp.roll(s_valid, 1))
        idx = jnp.arange(n)
        seg = jnp.cumsum(is_head.astype(jnp.int32)) - 1
        in_group = s_valid & ~is_head
        # Probe several earlier in-group rows: ANY earlier row with a
        # subset ghost bitset justifies the drop (its own drop reason, if
        # dropped, chains down to a kept subset).  A subset sorts before
        # its supersets, so probing the head plus a few nearby offsets
        # catches most dominated rows; leftovers only cost capacity.
        # The offset probes are static rolls guarded by a same-group
        # check: where the guard fails, the row ``off`` back lies before
        # the head, and the head is probed already.
        subsumed = jnp.zeros(n, dtype=bool)
        hit = in_group
        for c, head_c in zip(s_ghost, head_words(is_head, s_ghost)):
            hit &= (head_c & ~c) == 0
        subsumed |= hit
        for off in (1, 2, 4, 8, 16)[:N_PROBES]:
            hit = in_group & (idx >= off) & (jnp.roll(seg, off) == seg)
            for c in s_ghost:
                hit &= (jnp.roll(c, off) & ~c) == 0
            subsumed |= hit
        drop = drop | subsumed

    keep = s_valid & ~drop

    src_cols = s_cols + s_ghost + ([s_origin] if origin is not None else [])
    outs, out_valid, total = compact_rows(src_cols, keep, capacity)
    overflow = total > capacity
    if origin is None:
        return outs, out_valid, total, overflow
    new_rows = jnp.any(keep & (s_origin == 1))
    return outs[:-1], out_valid, total, overflow, new_rows, outs[-1]
