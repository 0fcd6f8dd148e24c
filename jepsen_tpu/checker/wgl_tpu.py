"""Device-tier linearizability engine — the point of this framework.

Replaces the reference's external knossos solver (invoked at
jepsen/src/jepsen/checker.clj:185-216) with a JAX search that runs entirely in
fixed-shape device buffers:

- A configuration is (pending-window bitmask, model state): uint32[MW] mask
  lanes + int32[S] state lanes (see prep.py for why that compression is
  complete).  The engine holds up to ``capacity`` configurations.
- The history is a stream of ENTER/RETURN events consumed by ``lax.scan`` in
  chunks; the host polls failure/overflow flags between chunks (early exit),
  so a refuted history stops in O(prefix).
- At a RETURN event the engine expands the configuration closure: a nested
  vmap applies the model step to every (configuration × pending op) pair —
  [C, W] parallel model steps per round — the few valid candidates are
  picked out of that grid by rank and select, and their union with the set
  is deduplicated and compacted by a multi-key sort (both in ops/dedup.py).
  Closure repeats to fixpoint (no genuinely-new kept candidate), then
  configurations lacking the returning op are pruned.
- Closure is skipped when the set is already closed: pruning on a bit
  preserves closedness (expansions of a surviving configuration also carried
  the bit), so closure is only needed after new ENTERs — the ``dirty`` flag.
- **Ghost subsumption** (the algorithmic contribution that moves the
  practical ceiling): slots held by *ghost* ops — crashed/info ops that
  never return — are never consulted by pruning, so (a) ghosts with equal
  op encodings are interchangeable and a config's ghost bits canonicalize
  to per-class counts, and (b) a config is dropped when one with the same
  non-ghost mask and state holds a subset of its ghost bits (it has a
  superset of the dropped config's futures and can re-derive it at any
  later closure).  Classic configuration search pays 2^crashes — the
  precise regime where the reference's knossos dies and histories must be
  kept short (jepsen/src/jepsen/independent.clj:1-7); with subsumption the
  cost is the antichain of ghost-count vectors, typically O(crashes).

Single-history frontier sharding across a device mesh lives in
jepsen_tpu.parallel; this module is mesh-agnostic but takes an optional
``axis_name`` so the closure can all_gather candidate rows and keep a
device-local slice of the deduplicated global set.
"""

from __future__ import annotations

import math
import threading
from collections import deque
from functools import partial
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jepsen_tpu.checker.prep import (
    EV_ENTER, EV_RETURN, PreparedHistory, WindowOverflow, prepare,
)
from jepsen_tpu.engine.cache import CACHE as _ENGINE_CACHE
from jepsen_tpu.engine.ladder import (
    MIN_EVENTS_BUCKET, pow2_at_least, round_window as _round_window,
)
from jepsen_tpu.engine.witness import (
    WITNESS_BUDGET, cpu_witness as _cpu_witness,
)
from jepsen_tpu.history import History
from jepsen_tpu.models.base import JaxModel
from jepsen_tpu.obs.recorder import instant, span
from jepsen_tpu.ops import dedup as _dedup
from jepsen_tpu.ops.cache import init_compilation_cache
from jepsen_tpu.ops.dedup import compact_grid, sort_dedup_compact

EV_NOP = 2

# Chunks dispatched ahead of the host's flag poll, so the device→host flags
# transfer of chunk i overlaps with the device computing chunk i+1.
LOOKAHEAD = 2

# (Round-3's EXPAND_BLOCK block-partitioned closure is gone: the delta
# closure with candidate compaction — see make_engine.closure — replaced
# per-block C*(B+1)-row sorts with one compacted C+NC-row merge per round,
# measured 20.2s -> well under the round-3 easy-tier wall on hardware.)

# Per-chunk closure work budget, in capacity x closure-iterations units.
# Closure cost is superlinear in live configuration count (more fixpoint
# rounds AND bigger sorts), so bounding the program by *event count* alone
# cannot bound its duration — a 32-event chunk was measured at 26 s during
# a 7k-config burst at capacity 16384, within sight of the TPU worker's
# ~60 s watchdog.  Instead each chunk carries an iteration budget
# (CLOSURE_WORK_BUDGET / capacity); when it runs out the remaining events
# gate to no-ops, the flags report how many events were really consumed,
# and the next dispatch resumes mid-chunk with a fresh budget.  A pause
# costs one more dispatch and its poll, nothing else: the chunk in flight
# behind the paused one starts at the pause (the event cursor rides on the
# device, see _get_run_chunk), so no speculative dispatch is thrown away
# and the poll overlaps the next chunk's compute.  (4M with the delta
# closure's compacted merges: per-iteration cost dropped ~4x vs the block
# closure, so the same watchdog margin affords more iterations per
# dispatch; measured easy-tier 7.5 s vs 7.8 s at 3M, hard tier unchanged,
# when a pause still discarded the chunk behind it.  At capacity 65536
# this is 61 iterations/dispatch, which stays inside the watchdog even
# when rounds take the full-grid fallback merge.)
CLOSURE_WORK_BUDGET = 4_000_000

#: Histories with at most this many ghost (crashed/info) ops run the LEAN
#: engine (``gwords=0``): ghost bits stay plain identity mask bits and the
#: whole subsumption pipeline — per-class canonicalization (a matmul),
#: compact-word expansion, and the subset probes — drops out of every merge.
#: Subsumption is an optimization, never a soundness condition: verdicts
#: are identical either way, only the explored-config count (and with it,
#: capacity pressure) changes.  Default 0 — measured on hardware, even 4
#: unsubsumed crashed CAS writes blew the 10k-op easy history from 819k to
#: 2.2M configs and forced capacity 16384 (18.5 s vs 6.6 s): the antichain
#: collapse matters at ANY ghost count, so lean is only for histories with
#: no ghosts at all, where it saves the machinery with nothing to lose.
LEAN_GHOST_MAX = 0


#: Largest capacity whose candidate compaction fetches rows by one-hot
#: matmul (see :func:`compaction_form`).  A shape rule, not a knob.
MATMUL_COMPACT_MAX_C = 8192

#: ``jax.named_scope`` names on the phases of :func:`make_engine`, so that a
#: device op in a trace says which phase it belongs to (metadata only: the
#: program is the same with or without them).  In order: the expansion
#: grid; candidate compaction; the merge's concatenate, ghost
#: canonicalisation and its inverse; the sort-dedup-compact call; the
#: return's prune; the single-round variant's event gather; the sharded
#: variant's ``all_gather`` of every shard's rows (``axis_name`` only).
ENGINE_SCOPES = ("wgl.expand", "wgl.compact", "wgl.merge", "wgl.sort_dedup",
                 "wgl.prune", "wgl.gather", "wgl.gather_shards")


# ---------------------------------------------------------------------------
# Counters (batch_stats idiom): what the static capacity spanned against
# what the frontier filled of it
# ---------------------------------------------------------------------------

#: the rung ``events_consumed_16k`` counts from: the last one before the
#: fission threshold (``engine/fission.py DEFAULT_THRESHOLD``)
TOP_RUNG = 16384

_STATS_LOCK = threading.Lock()


def _zero_stats() -> Dict[str, int]:
    return {"events_consumed": 0, "events_consumed_16k": 0,
            "cap_events": 0, "peak_events": 0}


_STATS = _zero_stats()


def check_stats() -> Dict[str, int]:
    """Sums over every poll a :func:`check` of this process accepted (one
    whose carry it went on from; a chunk re-run at a larger capacity is
    not one), weighted by the events the chunk consumed, so that closure
    rounds count on neither side: ``events_consumed``; ``events_consumed_16k``,
    those consumed at a capacity of ``TOP_RUNG`` or more; ``cap_events``,
    capacity x events (every merge of a chunk pays for the static
    capacity); ``peak_events``, the chunk's frontier high-water mark x
    events.  ``peak_events / cap_events`` is how full the capacity was
    over the stream: the single history's twin of ``batch_stats()``'s
    fill."""
    with _STATS_LOCK:
        return dict(_STATS)


def reset_check_stats() -> None:
    with _STATS_LOCK:
        _STATS.update(_zero_stats())


def closure_budget(capacity: int) -> int:
    """Closure iterations one chunk may spend at this capacity.

    ``capacity`` is the TOTAL rows a closure iteration sorts: callers whose
    per-iteration cost scales beyond a single engine's capacity (sharded:
    capacity_per_shard * n_shards gathered rows; batch: capacity * lanes)
    pass that product so one dispatch's wall-clock stays at the same bound
    everywhere."""
    return max(16, CLOSURE_WORK_BUDGET // max(1, capacity))


def compaction_form(C: int) -> str:
    """The form of ``ops.dedup.compact_grid`` an engine of capacity ``C``
    builds: static shape in, static choice out, no knob.  The one-hot
    matmul costs C x NC and wins up to C 8,192; above, two gathers of NC
    rows are cheaper (scripts/compact_bench.py on a v5e, table in PERF.md:
    at C 16,384 the matmul takes 1.7 times the blocks' time, at 4,096 the
    blocks 1.6 to 2.1 times the matmul's)."""
    return "matmul" if C <= MATMUL_COMPACT_MAX_C else "blocks"


def compact_candidates(step, mask, states, win_ops, cv, NC: int,
                       form: str):
    """The first ``NC`` valid cells of the [C, W] candidate grid ``cv`` as
    candidate rows, in row-major order: ``(cand_mask [NC, MW], cand_states
    [NC, S], valid [NC], total)``, rows past ``total`` zeroed and cells
    past ``NC`` dropped — bit for bit what ``ops.dedup.compact_rows`` gives
    on the flattened grid of ``mask[c] | slot_masks[w]`` and
    ``step(states[c], win_ops[w])``, without building either: a cell's
    columns are functions of (c, w), so ``compact_grid`` finds the pair
    and the ``NC`` successors are stepped here."""
    W, MW = cv.shape[1], mask.shape[1]
    (row_mask, row_states), slot, valid, total = compact_grid(
        cv, [mask, states], NC, form)
    bit = jnp.left_shift(jnp.uint32(1), (slot % 32).astype(jnp.uint32))
    slot_mask = jnp.where(jnp.arange(MW)[None, :] == (slot // 32)[:, None],
                          bit[:, None], jnp.uint32(0))
    # win_ops[slot] as a one-hot select over [NC, W]: dense, no gather
    at = slot[:, None] == jnp.arange(W)[None, :]
    f, a, b = (jnp.where(at, win_ops[None, :, i], 0).sum(1)
               for i in range(3))
    ns, _ok = jax.vmap(step)(row_states, f, a, b)
    keep = valid[:, None]
    return (jnp.where(keep, row_mask | slot_mask, jnp.uint32(0)),
            jnp.where(keep, ns.astype(jnp.int32), 0), valid, total)


# carry = (mask, states, valid, win_ops, active, dirty, failed, failed_op,
#          overflow, explored, rounds, peak, ghosts, budget, consumed,
#          cl_iters, fresh, cur_new)
# peak is the high-water mark of the distinct-configuration count since the
# driver last reset it: the capacity the search *actually* needed, which the
# host reads at chunk boundaries to pick the cheapest sufficient engine.
# ghosts is the uint32[MW] bitmask of window slots held by ops that never
# return (crashed/info ops): closure dedup subsumes on it (see closure).
# budget/consumed implement the per-dispatch work bound (see closure_budget);
# cl_iters is the cumulative fixpoint-iteration count of the *current paused
# closure* — it persists across pause/resume dispatches so the W+1
# convergence cap applies to the cumulative count, exactly as it did when a
# closure always ran inside one dispatch.  fresh ([W] bool) marks slots
# ENTERed since the last completed closure (delta round 0's slot gate);
# cur_new ([C] bool) marks rows added by the previous closure round (delta
# rounds' row gate) — both persist across pause/resume.


def make_engine(model: JaxModel, window: int, capacity: int,
                axis_name: Optional[str] = None, num_shards: int = 1,
                gwords: int = 1, work_budget: Optional[int] = None,
                single_round_closure: bool = False,
                steps_per_dispatch: int = 256):
    """Build the jittable (carry0, event_step, run_chunk) triple.

    ``window`` may be any positive slot count (candidate-row count — and so
    closure sort cost — scales with it, so callers pass the tightest window
    the history needs).  With ``axis_name``, buffers are device-local shards
    of a global set of ``capacity * num_shards`` configurations and closure
    dedup synchronizes via all_gather.  ``gwords`` is the number of compact
    ghost words (>= ceil(n_ghosts / 32) for the history being checked):
    ghost subsumption state sorts as ``gwords`` columns, not ceil(W/32) —
    keeping the big variadic sort narrow (wide sorts at high capacity have
    crashed the TPU compiler).  ``gwords=0`` builds the LEAN engine: ghost
    bits are ordinary identity mask bits, and canonicalization, compact
    expansion, and subsumption all vanish from the merge — sound for any
    history (subsumption is an optimization), chosen by drivers when the
    ghost count is small (chosen_gwords).

    ``single_round_closure`` builds the VMAP-SAFE variant for the batched
    (per-lane) driver: under vmap, ``lax.cond``/``switch`` execute EVERY
    branch for the whole batch, so the standard engine's three merge
    widths and per-return fixpoint loop multiply into a per-step cost that
    outruns the TPU watchdog (the round-2/3 batch-tier killer).  This mode
    runs exactly ONE closure round per scan step with ONE merge width
    (NC = C; a round whose candidates overflow the compacted buffer flags
    engine overflow and the lane escalates).  A RETURN whose closure
    hasn't converged parks in the pending-return register and later steps
    continue it one round at a time; each step gathers the lane's next
    event by the lane's own absolute ``consumed`` cursor (run_chunk's
    ``events`` is then the FULL stream and ``steps_per_dispatch`` fixes
    the program length), so per-step device work is constant, a
    dispatch's wall-clock is bounded by its step count, and vmapped lanes
    progress at fully independent rates with no idle steps.
    """
    assert window > 0
    # work_budget: None = capacity-scaled default; <= 0 = unlimited
    # (escape hatch for callers that manage their own bounds — the
    # shipped drivers all pass a real budget: the batch driver resumes
    # lanes at independent positions via per-lane consumed counts).
    if work_budget is None:
        work_budget = closure_budget(capacity)
    if work_budget <= 0:
        work_budget = 2**31 - 1
    # All three engine paths (single-chip, sharded, batched) build here;
    # enabling the persistent compilation cache at this shared layer
    # turns repeat compiles of any engine shape into disk loads.
    # Best-effort: a read-only fs must not break checking.
    init_compilation_cache()
    W, MW, S, C = window, (window + 31) // 32, model.state_size, capacity
    step = model.step

    # slot_masks[w] = uint32[MW] with bit w set.
    sm = np.zeros((W, MW), np.uint32)
    for w in range(W):
        sm[w, w // 32] = np.uint32(1) << np.uint32(w % 32)
    slot_masks = jnp.asarray(sm)

    def slot_bitmask(slot):
        word = slot // 32
        bit = jnp.left_shift(jnp.uint32(1), (slot % 32).astype(jnp.uint32))
        return jnp.where(jnp.arange(MW) == word, bit, jnp.uint32(0))

    def expand(states, win_ops):
        def per_config(st):
            def per_slot(op):
                ns, ok = step(st, op[0], op[1], op[2])
                return ns.astype(jnp.int32), ok
            return jax.vmap(per_slot)(win_ops)
        return jax.vmap(per_config)(states)  # [C, W, S], [C, W]

    def global_sum(x):
        return lax.psum(x, axis_name) if axis_name else x

    # Per-slot word index / shift for 2D bit extraction (the [N, W, MW]
    # broadcast form would materialize gigabytes at large C*(W+1)).
    GW = gwords
    word_of = jnp.arange(W) // 32
    shift_of = (jnp.arange(W) % 32).astype(jnp.uint32)

    def canonical_compact(mask_words, win_ops):
        """Canonical *compact* ghost state per row: same-encoding ghosts
        are interchangeable (identical step functions, none ever returns),
        so only the per-class COUNT of linearized ghosts matters.  The
        canonical form sets, for each class, the first ``count`` bits of
        the class's contiguous range in a ceil(n_ghosts/32)-word compact
        layout (prep assigns ``gpos`` = class offset + rank)."""
        cls = win_ops[:, 3]                  # [W] class id (slot) or -1
        rank = win_ops[:, 4]                 # [W] rank within class
        gpos = win_ops[:, 5]                 # [W] compact bit position
        is_g = cls >= 0
        bits = (jnp.take(mask_words, word_of, axis=1)
                >> shift_of[None, :]) & 1
        # counts[n, c] = number of class-c ghost bits set in row n (matmul
        # on the MXU; counts <= W, exact in float32)
        onehot = ((cls[None, :] == jnp.arange(W)[:, None]) &
                  is_g[None, :]).astype(jnp.float32)       # [W(cls), W(slot)]
        counts = bits.astype(jnp.float32) @ onehot.T       # [N, W]
        cnt_for_slot = jnp.take(counts, jnp.clip(cls, 0, W - 1), axis=1)
        cbits = (is_g[None, :] & (rank[None, :].astype(jnp.float32)
                                  < cnt_for_slot)).astype(jnp.uint32)
        out = []
        for j in range(GW):
            w = jnp.where(is_g & (gpos // 32 == j),
                          jnp.left_shift(jnp.uint32(1),
                                         (gpos % 32).astype(jnp.uint32)),
                          jnp.uint32(0))
            out.append((cbits * w[None, :]).sum(1, dtype=jnp.uint32))
        return jnp.stack(out, axis=-1)                     # [N, GW]

    def expand_compact(compact, win_ops):
        """Inverse of :func:`canonical_compact`: slot-space ghost words
        from a compact row (bit gpos[s] -> slot bit s)."""
        cls = win_ops[:, 3]
        gpos = win_ops[:, 5]
        is_g = cls >= 0
        word = jnp.take(compact, jnp.clip(gpos // 32, 0, GW - 1), axis=1)
        bits = ((word >> (gpos % 32).astype(jnp.uint32)[None, :]) & 1) \
            * is_g[None, :].astype(jnp.uint32)
        out = []
        for i in range(MW):
            sl = slice(32 * i, min(32 * i + 32, W))
            powers = (jnp.uint32(1) << shift_of[sl])
            out.append((bits[:, sl] * powers[None, :]).sum(
                1, dtype=jnp.uint32))
        return jnp.stack(out, axis=-1)                     # [N, MW]

    def closure(mask, states, valid, win_ops, active, ghosts, overflow,
                budget, it0, fresh, cur_new, enable=None):
        # Dedup treats the ghost-slot part of the mask as a *subsumption*
        # column, not an identity column: ghost ops never return, so their
        # bits are never consulted by pruning, and a config whose ghost set
        # contains another's (same non-ghost mask, same state) has a subset
        # of its futures and is re-derivable from it at any later closure.
        # Together with per-class canonicalization this turns the
        # 2^crashes configuration blowup that kills knossos into
        # O(crashes) — see BENCH ghost tiers.
        #
        # **Delta (semi-naive) evaluation** — the round-4 speedup.  The set
        # is closed between closures, so round 0 only expands (all rows) x
        # (slots ENTERed since the last closure — ``fresh``), and round
        # r>0 only expands (rows kept NEW by round r-1 — ``cur_new``) x
        # (all active slots).  Soundness: S was closed over the old slots;
        # S x old-slots candidates are already present-or-subsumed, and a
        # row dropped by subsumption is simulated by its (kept, expanded)
        # dropper, whose successors subsume the dropped row's successors.
        #
        # **Candidate compaction** — the valid candidates of a round are
        # usually far fewer than the C*W expansion grid (by the delta rule
        # the grid is all rows x the one or two fresh slots, then the few
        # new rows x all slots), so they compact into a small buffer and
        # the merge sorts C + NC rows instead of C*(W+1).  The compaction
        # is rank and select (compact_candidates): a candidate is
        # mask[c] | slot_masks[w] and step(states[c], win_ops[w]), so only
        # its (c, w) is found, from a running count over the grid's rows,
        # and the NC successors are stepped from there; the grid itself
        # is validity bits and is never built as rows (building them and
        # stable-sorting all C*W to move the few to the front costs
        # 0.52 ms a round at C 4,096 x W 60 against 0.03 ms, PERF.md).
        # Four merge widths are compiled (NC = C/2, C, 4C, and the full
        # C*W grid, the only one that still builds every row) and selected
        # per round by the (shard-uniform) candidate count.
        #
        # ``budget`` caps the fixpoint iterations of THIS call: a closure
        # that runs out pauses (returns converged=False) with the partial —
        # but sound, monotone — set; the caller must then keep the dirty
        # flag, not consume the event, and let the host resume the same
        # RETURN in a fresh dispatch, where closure continues from the
        # partial set to the same fixpoint.  This makes the per-dispatch
        # iteration bound *tight* (<= budget), not budget + window.
        count0 = global_sum(valid.sum())

        def merge_rows(mask, states, valid, cand_mask, cand_states,
                       cand_valid, ovf, round_new=None):
            """Dedup/compact the union of the existing set and this
            round's candidate rows; returns the new set, per-row newness,
            and fixpoint/overflow signals.

            ``round_new`` (bool[C], tiled-fold path only) marks existing
            rows that were added by an EARLIER fold of the same closure
            round: they must stay in the returned ``cur_new`` (the next
            round's delta frontier) but must not re-trigger the new-rows
            fixpoint signal.  Encoded as origin 2 — dedup's ``new_rows``
            only counts origin 1 (candidates), while the returned frontier
            keeps any origin >= 1."""
            nc = cand_valid.shape[0]
            with jax.named_scope("wgl.merge"):
                all_mask = jnp.concatenate([mask, cand_mask])
                all_states = jnp.concatenate([states, cand_states])
                all_valid = jnp.concatenate([valid, cand_valid])
                exist_origin = (jnp.zeros(C, jnp.int32)
                                if round_new is None
                                else 2 * round_new.astype(jnp.int32))
                origin = jnp.concatenate([exist_origin,
                                          jnp.ones(nc, jnp.int32)])
                if axis_name is not None:
                    with jax.named_scope("wgl.gather_shards"):
                        all_mask, all_states, all_valid, origin = (
                            lax.all_gather(x, axis_name, tiled=True)
                            for x in (all_mask, all_states, all_valid,
                                      origin))
                if GW:
                    keyed = all_mask & ~ghosts[None, :]
                    gpart = canonical_compact(all_mask & ghosts[None, :],
                                              win_ops)
                    gcols = [gpart[:, i] for i in range(GW)]
                else:
                    # Lean engine: ghost bits are identity bits like any
                    # other; no canonicalization column, no subset
                    # subsumption.
                    keyed = all_mask
                    gcols = []
                cols = ([keyed[:, i] for i in range(MW)]
                        + [all_states[:, i] for i in range(S)])
            gcap = C * num_shards
            with jax.named_scope("wgl.sort_dedup"):
                out_cols, out_valid, total, ovf2, new_rows, out_orig = \
                    sort_dedup_compact(cols, all_valid, gcap,
                                       ghost_cols=gcols, origin=origin)
            with jax.named_scope("wgl.merge"):
                new_keyed = jnp.stack(out_cols[:MW], -1)
                new_states = jnp.stack(out_cols[MW:MW + S], -1)
                if GW:
                    new_compact = jnp.stack(out_cols[MW + S:], -1)
                    new_mask = new_keyed | expand_compact(new_compact,
                                                          win_ops)
                else:
                    new_mask = new_keyed
                cur_new2 = (out_orig >= 1) & out_valid
            if axis_name is not None:
                start = lax.axis_index(axis_name) * C
                new_mask = lax.dynamic_slice_in_dim(new_mask, start, C)
                new_states = lax.dynamic_slice_in_dim(new_states, start, C)
                out_valid = lax.dynamic_slice_in_dim(out_valid, start, C)
                cur_new2 = lax.dynamic_slice_in_dim(cur_new2, start, C)
            return new_mask, new_states, out_valid, cur_new2, total, \
                new_rows, ovf | ovf2

        def compact_to(mask, states, cv, NC):
            """The round's valid candidates as NC rows (rank and select
            over the grid, see compact_candidates)."""
            with jax.named_scope("wgl.compact"):
                cm, cs, cvv, _total = compact_candidates(
                    step, mask, states, win_ops, cv, NC,
                    compaction_form(C))
            return cm, cs, cvv

        def cond(c):
            _, _, _, _, _, changed, ovf, it = c
            return changed & ~ovf & (it < W + 1) & (it - it0 < budget)

        def body(c):
            mask, states, valid, cur_new, count, _, ovf, it = c
            # Full-window expansion grid, gated by the delta rule.
            with jax.named_scope("wgl.expand"):
                _, ok = expand(states, win_ops)                # [C, W]
                has = ((mask[:, None, :]
                        & slot_masks[None, :, :]) != 0).any(-1)
                round0 = it == 0
                row_gate = jnp.where(round0, valid, valid & cur_new)
                slot_gate = jnp.where(round0, active & fresh, active)
                cv = row_gate[:, None] & slot_gate[None, :] & ~has & ok
                if enable is not None:  # lane-level gate (single-round)
                    cv = cv & enable
                nv = cv.sum().astype(jnp.int32)
            nv_max = (lax.pmax(nv, axis_name)
                      if axis_name is not None else nv)
            some = global_sum(nv) > 0

            def merge_compacted(NC):
                def f(args):
                    mask, states, valid, cur_new, ovf = args
                    cm, cs, cvv = compact_to(mask, states, cv, NC)
                    return merge_rows(mask, states, valid, cm, cs, cvv, ovf)
                return f

            def grid_rows(mask, states):
                """Every cell of the grid as a candidate row: only the
                full-width merges build it (the compacted ones step their
                NC successors themselves), so it is made inside their
                branch and costs the usual round nothing."""
                with jax.named_scope("wgl.expand"):
                    cand_states, _ = expand(states, win_ops)   # [C, W, S]
                    cand_mask = mask[:, None, :] | slot_masks[None, :, :]
                return (cand_mask.reshape(C * W, MW),
                        cand_states.reshape(C * W, S))

            def merge_full(args):
                mask, states, valid, cur_new, ovf = args
                flat_mask, flat_states = grid_rows(mask, states)
                return merge_rows(mask, states, valid, flat_mask,
                                  flat_states, cv.reshape(C * W), ovf)

            def merge_full_tiled(args):
                """Full-grid merge as a fold over candidate tiles, each
                merge kept under ops.dedup.WIDE_SORT_ROWS so every sort
                takes the single-variadic-sort path.  One C*(W+1)-row
                merge at capacity 65536 exceeds the threshold and falls
                back to the _lex_perm sort chain, whose ~11 full-size
                sort passes compile for tens of minutes on TPU — and
                lax.switch compiles ALL branches, so every 65536-capacity
                engine paid that even when the full fallback never ran.
                The fold's loop body compiles ONCE at (C + tile) rows.

                Soundness of folding: the existing set participates in
                every fold, so duplicates against it are always dropped;
                a candidate duplicating an earlier fold's survivor sees
                that survivor as an existing row.  ``round_new`` threads
                the this-round frontier through the folds (origin-2
                protocol in merge_rows)."""
                mask, states, valid, cur_new, ovf = args
                flat_mask, flat_states = grid_rows(mask, states)
                flat_cv = cv.reshape(C * W)
                budget_rows = max(_dedup.WIDE_SORT_ROWS // num_shards - C,
                                  C)
                K = -(-(C * W) // budget_rows)  # ceil
                T = -(-(C * W) // K)
                pad = K * T - C * W
                if pad:
                    flat_mask = jnp.concatenate(
                        [flat_mask, jnp.zeros((pad, MW), flat_mask.dtype)])
                    flat_states = jnp.concatenate(
                        [flat_states, jnp.zeros((pad, S),
                                                flat_states.dtype)])
                    flat_cv = jnp.concatenate(
                        [flat_cv, jnp.zeros(pad, flat_cv.dtype)])

                def fold(i, acc):
                    mask, states, valid, rnew, total, newr, ovf = acc
                    tm = lax.dynamic_slice_in_dim(flat_mask, i * T, T)
                    ts = lax.dynamic_slice_in_dim(flat_states, i * T, T)
                    tv = lax.dynamic_slice_in_dim(flat_cv, i * T, T)
                    m2, s2, v2, rnew2, total2, nr2, ovf2 = merge_rows(
                        mask, states, valid, tm, ts, tv, ovf,
                        round_new=rnew)
                    return (m2, s2, v2, rnew2, total2, newr | nr2, ovf2)

                init = (mask, states, valid, jnp.zeros_like(valid),
                        count, jnp.bool_(False), ovf)
                m2, s2, v2, rnew, total, newr, ovf2 = lax.fori_loop(
                    0, K, fold, init)
                return m2, s2, v2, rnew, total, newr, ovf2

            def do(args):
                if single_round_closure:
                    # vmap runs every switch branch, so the batched engine
                    # gets ONE width; compact_to silently truncates past
                    # NC, which would be unsound — flag overflow instead
                    # so the driver escalates the lane.
                    out = merge_compacted(C)(args)
                    return out[:6] + (out[6] | (nv > C),)
                # Merge width by (shard-uniform) candidate volume: the
                # typical round's candidates are at most the live count
                # (well under C/2 in steady state), burst rounds take the
                # C or 4C buffers, and the full grid is the rare fallback.
                half = max(1, C // 2)
                full = (merge_full_tiled
                        if num_shards * C * (W + 1) > _dedup.WIDE_SORT_ROWS
                        else merge_full)
                sel = jnp.where(nv_max <= half, 0,
                                jnp.where(nv_max <= C, 1,
                                          jnp.where(nv_max <= 4 * C, 2,
                                                    3)))
                return lax.switch(sel, [merge_compacted(half),
                                        merge_compacted(C),
                                        merge_compacted(4 * C),
                                        full], args)

            def skip(args):
                mask, states, valid, cur_new, ovf = args
                return (mask, states, valid,
                        jnp.zeros_like(cur_new), count,
                        jnp.bool_(False), ovf)

            mask, states, valid, cur_new, count, changed, ovf = lax.cond(
                some, do, skip, (mask, states, valid, cur_new, ovf))
            # Fixpoint signal: a kept candidate, NOT a count delta —
            # subsumption can drop an existing row in the round that adds a
            # new one, leaving the count level while the set moved.
            return (mask, states, valid, cur_new, count, changed, ovf,
                    it + 1)

        init = (mask, states, valid, cur_new, count0, jnp.bool_(True),
                overflow, it0)
        if single_round_closure:
            # One round per call.  NOTE the consume-on-arrival design: a
            # RETURN is consumed the step it arrives and parked in the
            # pending-return register; successive steps run one round
            # each until convergence lands the prune.  The host must
            # therefore treat a lane as LIVE while its stalled flag is
            # set even if its cursor passed the stream end (flags[4]).
            mask, states, valid, cur_new, count, changed, overflow, \
                it_fin = body(init)
        else:
            (mask, states, valid, cur_new, count, changed, overflow,
             it_fin) = lax.while_loop(cond, body, init)
        # Exit reasons: fixpoint (~changed), the W+1 cumulative chain-depth
        # cap (treated as converged — matches the pre-budget behavior), or
        # budget exhaustion — the only pause case.
        converged = ~changed | (it_fin >= W + 1)
        return mask, states, valid, cur_new, count, overflow, it_fin, \
            converged

    def event_step(carry, ev):
        (mask, states, valid, win_ops, active, dirty, failed, failed_op,
         overflow, explored, rounds, peak, ghosts, budget, consumed,
         cl_iters, fresh, cur_new) = carry
        kind, slot, f, a, b, op_id, is_ghost, gcls, grank, gpos = (
            ev[0], ev[1], ev[2], ev[3], ev[4], ev[5], ev[6], ev[7], ev[8],
            ev[9])
        # budget > 0: an exhausted closure budget pauses the chunk — the
        # remaining events gate to no-ops and the host resumes them in a
        # fresh dispatch (consumed tells it where).  Bounds one XLA
        # program's duration by *work*, which event counts cannot.
        alive = ~failed & ~overflow & (budget > 0)

        def do_enter(c):
            (mask, states, valid, win_ops, active, dirty, failed, failed_op,
             overflow, explored, rounds, peak, ghosts, budget, consumed,
             cl_iters, fresh, cur_new) = c
            win_ops2 = win_ops.at[slot].set(
                jnp.stack([f, a, b, gcls, grank, gpos]))
            active2 = active.at[slot].set(True)
            fresh2 = fresh.at[slot].set(True)  # delta-closure round 0 gate
            # A crashed op holds its slot forever; its bit becomes a
            # subsumption column in closure dedup.  (Slots of crashed ops
            # are never freed, so the bit can't later mean a live op.)
            ghosts2 = jnp.where(is_ghost == 1,
                                ghosts | slot_bitmask(slot), ghosts)
            return (mask, states, valid, win_ops2, active2, jnp.bool_(True),
                    failed, failed_op, overflow, explored, rounds, peak,
                    ghosts2, budget, consumed + 1, cl_iters, fresh2,
                    cur_new)

        def do_return(c):
            (mask, states, valid, win_ops, active, dirty, failed, failed_op,
             overflow, explored, rounds, peak, ghosts, budget, consumed,
             cl_iters, fresh, cur_new) = c

            def with_closure(args):
                (mask, states, valid, cur_new, overflow, rounds, peak,
                 budget, cl_iters) = args
                (mask, states, valid, cur_new, count, overflow, it_fin,
                 converged) = closure(mask, states, valid, win_ops, active,
                                      ghosts, overflow, budget, cl_iters,
                                      fresh, cur_new)
                iters = it_fin - cl_iters
                return (mask, states, valid, cur_new, overflow,
                        rounds + iters, jnp.maximum(peak, count),
                        budget - iters, it_fin, converged, count)

            def no_closure(args):
                (mask, states, valid, cur_new, overflow, rounds, peak,
                 budget, cl_iters) = args
                # Set already closed (no ENTER since the last closure):
                # nothing to add to ``explored`` — count sentinel -1.
                return (mask, states, valid, cur_new, overflow, rounds,
                        peak, budget, cl_iters, jnp.bool_(True),
                        jnp.int32(-1))

            (mask, states, valid, cur_new, overflow, rounds, peak, budget,
             cl_iters, converged, count) = lax.cond(
                dirty, with_closure, no_closure,
                (mask, states, valid, cur_new, overflow, rounds, peak,
                 budget, cl_iters))

            def do_prune(args):
                # Closure reached fixpoint inside the budget: prune configs
                # lacking the returning op and consume the event.
                (mask, states, valid, active, dirty, failed, failed_op,
                 explored, consumed, cl_iters, fresh) = args
                with jax.named_scope("wgl.prune"):
                    bm = slot_bitmask(slot)
                    has = ((mask & bm[None, :]) != 0).any(-1)
                    valid2 = valid & has
                    n_surv = global_sum(valid2.sum())
                    newly_failed = n_surv == 0
                    failed_op2 = jnp.where(newly_failed & ~failed, op_id,
                                           failed_op)
                    mask2 = mask & ~bm[None, :]
                    active2 = active.at[slot].set(False)
                return (mask2, states, valid2, active2, jnp.bool_(False),
                        failed | newly_failed, failed_op2,
                        explored + jnp.maximum(count, 0), consumed + 1,
                        jnp.int32(0), jnp.zeros_like(fresh))

            def do_pause(args):
                # Budget ran out mid-fixpoint: keep the partial (sound,
                # monotone) set, keep dirty, do NOT consume — the host
                # resumes this same RETURN in a fresh dispatch and the
                # closure continues where it left off (cl_iters carries the
                # cumulative iteration count, cur_new the delta frontier).
                return args

            (mask, states, valid, active, dirty, failed, failed_op, explored,
             consumed, cl_iters, fresh) = lax.cond(
                converged, do_prune, do_pause,
                (mask, states, valid, active, dirty, failed, failed_op,
                 explored, consumed, cl_iters, fresh))
            return (mask, states, valid, win_ops, active, dirty, failed,
                    failed_op, overflow, explored, rounds, peak, ghosts,
                    budget, consumed, cl_iters, fresh, cur_new)

        def do_nop(c):
            return c[:14] + (c[14] + 1,) + c[15:]  # consumed += 1

        def apply(c):
            return lax.switch(kind, [do_enter, do_return, do_nop], c)

        new_carry = lax.cond(alive, apply, lambda c: c, carry)
        return new_carry, None

    def event_step_single(carry, ev):
        """Mask-native event step for the vmapped batch engine: no
        cond/switch (vmap executes every branch), exactly ONE closure
        round per step.  ``ev`` is the lane's NEXT unconsumed event
        (gathered by the lane's own ``consumed`` cursor — see
        run_chunk's single-round variant), so lanes never need positional
        alignment: a step either continues a pending return's closure
        (pr_slot/pr_op, carry[18:20]) one round, or applies the next
        event; every step makes real progress for every lane."""
        (mask, states, valid, win_ops, active, dirty, failed, failed_op,
         overflow, explored, rounds, peak, ghosts, budget, consumed,
         cl_iters, fresh, cur_new, pr_slot, pr_op) = carry
        kind, slot = ev[0], ev[1]
        f, a, b, op_id = ev[2], ev[3], ev[4], ev[5]
        is_ghost, gcls, grank, gpos = ev[6], ev[7], ev[8], ev[9]
        alive = ~failed & ~overflow
        stalled = pr_slot >= 0

        # -- Phase A: one closure round for the pending return, or for an
        # incoming RETURN (at most one closure user per step).
        ret_in = alive & ~stalled & (kind == EV_RETURN)
        c_active = (alive & stalled) | ret_in
        c_slot = jnp.where(stalled, pr_slot, slot)
        c_op = jnp.where(stalled, pr_op, op_id)
        work = c_active & dirty
        (mask, states, valid, cur_new, count, overflow, it_fin,
         converged) = closure(mask, states, valid, win_ops, active, ghosts,
                              overflow, jnp.int32(2**30), cl_iters, fresh,
                              cur_new, enable=work)
        rounds = rounds + jnp.where(work, it_fin - cl_iters, 0)
        peak = jnp.maximum(peak, jnp.where(work, count, 0))
        converged = converged | ~dirty
        finish = c_active & converged
        with jax.named_scope("wgl.prune"):
            bm = slot_bitmask(c_slot)
            has = ((mask & bm[None, :]) != 0).any(-1)
            valid = jnp.where(finish, valid & has, valid)
            newly_failed = finish & (global_sum(valid.sum()) == 0)
            failed_op = jnp.where(newly_failed & ~failed, c_op, failed_op)
            failed = failed | newly_failed
            mask = jnp.where(finish, mask & ~bm[None, :], mask)
            active = jnp.where(finish, active.at[c_slot].set(False),
                               active)
        explored = explored + jnp.where(finish & work, count, 0)
        fresh = jnp.where(finish, jnp.zeros_like(fresh), fresh)
        cl_iters = jnp.where(finish, 0,
                             jnp.where(c_active, it_fin, cl_iters))
        dirty = dirty & ~finish
        new_stall = c_active & ~converged & ~stalled
        pr_slot = jnp.where(finish, -1, jnp.where(new_stall, slot, pr_slot))
        pr_op = jnp.where(finish, -1, jnp.where(new_stall, op_id, pr_op))

        # -- Phase B: ENTER/NOP apply only when the lane entered the step
        # un-stalled (a pending return's prune must land before an ENTER
        # can reuse its just-freed slot — the ENTER waits a step).
        entering = alive & ~stalled & (kind == EV_ENTER)
        row = jnp.stack([f, a, b, gcls, grank, gpos])
        win_ops = jnp.where(entering, win_ops.at[slot].set(row), win_ops)
        active = jnp.where(entering, active.at[slot].set(True), active)
        fresh = jnp.where(entering, fresh.at[slot].set(True), fresh)
        ghosts = jnp.where(entering & (is_ghost == 1),
                           ghosts | slot_bitmask(slot), ghosts)
        dirty = dirty | entering

        consumed = consumed + jnp.where(
            entering | ret_in | (alive & ~stalled & (kind == EV_NOP)),
            1, 0)
        return (mask, states, valid, win_ops, active, dirty, failed,
                failed_op, overflow, explored, rounds, peak, ghosts,
                budget, consumed, cl_iters, fresh, cur_new, pr_slot,
                pr_op), None

    def _init_win_ops(w):
        # columns: f, a, b, ghost-class (-1 = not a ghost), ghost-rank,
        # compact ghost bit position
        return jnp.zeros((w, 6), jnp.int32).at[:, 3].set(-1)

    def carry0():
        states = jnp.tile(jnp.asarray(model.init_state_array())[None, :], (C, 1))
        return (jnp.zeros((C, MW), jnp.uint32),            # mask
                states,                                    # states
                jnp.arange(C) == 0 if axis_name is None    # valid: one config
                else None,                                 # (set by caller)
                _init_win_ops(W),                          # win_ops
                jnp.zeros(W, dtype=bool),                  # active
                jnp.bool_(False),                          # dirty
                jnp.bool_(False),                          # failed
                jnp.int32(-1),                             # failed_op
                jnp.bool_(False),                          # overflow
                jnp.int32(0),                              # explored
                jnp.int32(0),                              # closure rounds
                jnp.int32(1),                              # peak config count
                jnp.zeros(MW, jnp.uint32),                 # ghost slots
                jnp.int32(work_budget),                    # closure budget
                jnp.int32(0),                              # events consumed
                jnp.int32(0),                              # paused-closure its
                jnp.zeros(W, dtype=bool),                  # fresh slots
                jnp.zeros(C, dtype=bool)) + (              # delta frontier
                (jnp.int32(-1), jnp.int32(-1))             # pending return
                if single_round_closure else ())

    def run_chunk(carry, events):
        # Reset the peak to the live count on entry, and the work budget /
        # consumed-event counter to fresh values (device-side: the host
        # reads all per-chunk scalars without extra round-trips); scan the
        # events; pack the scalars the host polls into ONE int32 vector so
        # a chunk boundary costs a single device→host transfer.  cl_iters /
        # fresh / cur_new (carry[15:]) are NOT reset: they belong to a
        # possibly-paused closure.
        live0 = global_sum(carry[2].sum()).astype(jnp.int32)
        if single_round_closure:
            # ``events`` is the lane's FULL (padded) stream; ``consumed``
            # is the lane's ABSOLUTE cursor (not reset per dispatch) and
            # each of the fixed per-dispatch steps gathers the cursor's
            # event — no slicing, no alignment, no idle steps.
            carry = carry[:11] + (live0, carry[12],
                                  jnp.int32(work_budget)) + carry[14:]
            n_ev = events.shape[0]

            def gather_step(c, _):
                with jax.named_scope("wgl.gather"):
                    pos = jnp.minimum(c[14], n_ev - 1)
                    ev = lax.dynamic_index_in_dim(events, pos,
                                                  keepdims=False)
                return event_step_single(c, ev)

            carry, _ = lax.scan(gather_step, carry, None,
                                length=steps_per_dispatch)
        else:
            carry = carry[:11] + (live0, carry[12],
                                  jnp.int32(work_budget), jnp.int32(0)) \
                + carry[15:]
            carry, _ = lax.scan(event_step, carry, events)
        stalled = (carry[18] >= 0) if single_round_closure else jnp.int32(0)
        flags = jnp.stack([carry[6].astype(jnp.int32),   # failed
                           carry[8].astype(jnp.int32),   # overflow
                           carry[11],                    # peak configs
                           carry[14],                    # events consumed
                           # pending return still unconverged: the host
                           # MUST keep dispatching even when the cursor
                           # passed the stream (its prune hasn't landed)
                           jnp.asarray(stalled, jnp.int32)])
        return carry, flags

    return carry0, event_step, run_chunk


# ---------------------------------------------------------------------------
# Host driver
# ---------------------------------------------------------------------------

_SLICE_CACHE: Dict[int, Any] = {}


def _chunk_slicer(chunk: int, axis: int = 0):
    """Jitted device-side slicer (index traced, not baked): one compile per
    (chunk size, axis), zero host->device payload per dispatch.  Static
    python slice bounds would instead compile one slice op per chunk
    *index*."""
    key = (chunk, axis)
    if key not in _SLICE_CACHE:
        _SLICE_CACHE[key] = jax.jit(
            lambda buf, i: lax.dynamic_slice_in_dim(buf, i, chunk, axis))
    return _SLICE_CACHE[key]


def _get_run_chunk(model: JaxModel, window: int, capacity: int,
                   gwords: int, chunk: int):
    """``(carry0, run)`` of the single-history driver: ``run(carry, cursor,
    ev_dev) -> (carry', cursor + consumed, flags)`` is ONE program that
    cuts its own ``chunk`` events out of the staged stream at ``cursor``,
    a device scalar, and scans them with ``make_engine``'s ``run_chunk``.
    The position so travels with the carry: a chunk enqueued behind one
    that the closure budget paused starts where that one stopped, without
    the host having seen where that is."""
    # Same-named registry models share step semantics; keying on the name +
    # variant + initial state (not the closure id) lets every get_model()
    # call reuse one compiled engine.  Entries live in the shared bounded
    # engine cache (engine.cache) next to the batched engines — one LRU,
    # one stats endpoint, one eviction policy for every compiled engine in
    # the process; the "singlev" tag keeps single- and batch-mode keys
    # from colliding.
    key = ("singlev", model.name, model.variant, model.state_size,
           tuple(model.init_state_array().tolist()), window, capacity,
           gwords, chunk, _dedup.N_PROBES, _dedup.WIDE_SORT_ROWS,
           _dedup.SUBSUME, CLOSURE_WORK_BUDGET)
    hit = _ENGINE_CACHE.get(key)
    if hit is not None:
        return hit
    carry0, _, run_chunk = make_engine(model, window, capacity,
                                       gwords=gwords)

    def run_at(carry, cursor, ev_dev):
        # The barrier is for the TPU compiler, and measured (PERF.md, PR
        # 32): with a bare slice of the parameter, the capacity-1,024
        # engine's two usual merge branches lost the prefetch of mask and
        # states into fast memory (+0.85% on a clean 10k-op history, 12
        # ms of device time a call); behind the barrier they keep it, and
        # the 4,096 and 16,384 engines compile to the same loops either
        # way.
        events = lax.dynamic_slice_in_dim(
            *lax.optimization_barrier((ev_dev, cursor)), chunk)
        carry, flags = run_chunk(carry, events)
        return carry, cursor + flags[3], flags

    # No donation: the overflow-resume path re-uses the chunk-boundary
    # carry snapshot after the call, and the buffers are small anyway.
    from jepsen_tpu.obs.hist import timed_first_call
    run = timed_first_call(
        jax.jit(run_at),
        f"compile:singlev:{model.name}:w{window}:c{capacity}")
    return _ENGINE_CACHE.put(key, (carry0, run))


def events_array(p: PreparedHistory, chunk: int) -> np.ndarray:
    """[E_padded, 10] int32 event stream, NOP-padded to a chunk multiple."""
    e = len(p)
    ep = max(chunk, ((e + chunk - 1) // chunk) * chunk)
    ev = np.full((ep, 10), 0, np.int32)
    ev[:, 0] = EV_NOP
    ev[:e, 0] = p.kind
    ev[:e, 1] = p.slot
    ev[:e, 2] = p.f
    ev[:e, 3] = p.a
    ev[:e, 4] = p.b
    ev[:e, 5] = p.op_id
    ev[:e, 6] = p.ghost
    ev[:e, 7] = p.gcls
    ev[:e, 8] = p.grank
    ev[:e, 9] = p.gpos
    return ev


def ghost_words(p: PreparedHistory) -> int:
    """Compact ghost words an engine needs for this history."""
    return max(1, (int(p.n_ghosts) + 31) // 32)


def chosen_gwords(p: PreparedHistory) -> int:
    """Ghost words the driver actually builds the engine with: 0 (the lean,
    subsumption-free engine) when the history's ghost count is small enough
    that the ≤2^ghosts extra configurations are cheaper than the ghost
    machinery's per-merge op chain (see LEAN_GHOST_MAX), else the compact
    word count.  Single source of truth for check(), the bench warm-up, and
    the batch/sharded drivers — warming a different engine shape than the
    timed path dispatches would re-compile inside the timed run."""
    if int(p.n_ghosts) <= LEAN_GHOST_MAX:
        return 0
    return ghost_words(p)


def chunk_for_capacity(capacity: int, base_chunk: int) -> int:
    """Events per dispatch at ``capacity``.

    Round 3 statically shrank the chunk as capacity grew (512*1024
    capacity*events per dispatch) to keep one XLA program inside the TPU
    worker's ~60 s watchdog — and the resulting per-dispatch host polls
    (128-event chunks at capacity 4096, ~80 polls, each a device→host
    round trip) became the easy-tier bottleneck.  The per-chunk closure
    work budget (closure_budget: iterations scaled down as capacity grows,
    enforced *inside* a single closure's fixpoint loop with mid-event
    pause/resume) now bounds a dispatch's wall-clock tightly at any
    capacity, so the chunk no longer needs to shrink: a capacity
    escalation keeps the same dispatch granularity and the next dispatch
    just goes on mid-chunk whenever the engine pauses."""
    return base_chunk


#: Auto-chunk rule (chunk=None): histories unlikely to escalate take the
#: COARSE chunk — fewer chunk-boundary polls, each a device→host round
#: trip — while escalation-prone ones keep the fine chunk, whose tighter
#: capacity adaptation wins once bursts drive capacity changes (coarser
#: chunks discard more speculative work per change).  Escalation
#: pressure has two measured drivers: ghosts (each pending crashed op
#: can double the config set) and multi-lane state (wider state, bigger
#: spaces).  Measured on hardware, 10k-op histories, on the code from
#: before PR 21 (the merge has lost a C x W sort and its gathers since,
#: so read these as the rule's origin, not as today's times):
#: register-easy (~3 ghosts, 1 lane) 3.08 s at 1024 vs 3.81 s at 512;
#: register-hard (56 ghosts) 8.7 s at 512 vs 10.3 s at 1024;
#: multi-register (7 ghosts but 3 state lanes, escalates to 16384)
#: 36.2 s at 512 vs 40.6 s at 1024.  Today (PR 31, one TPU v5 lite
#: chip) that multi-register history takes 7.1 s a call at 512
#: (`multireg10k.offline`; PERF.md section 6), 5.6 s of it at 16384,
#: where the closure budget, not the chunk, ends a dispatch after about
#: 150 events.  Since PR 32 such a pause discards nothing (the chunk
#: behind it goes on from the pause), so there the chunk only sets how
#: many events a dispatch may cover at most; 1024 has not been timed
#: again.
AUTO_CHUNK_FINE = 512
AUTO_CHUNK_COARSE = 1024
AUTO_CHUNK_GHOST_MAX = 8


def auto_chunk(p: PreparedHistory, model: JaxModel) -> int:
    """Events per dispatch for this history under the auto-chunk rule."""
    return (AUTO_CHUNK_COARSE
            if p.n_ghosts <= AUTO_CHUNK_GHOST_MAX and model.state_size == 1
            else AUTO_CHUNK_FINE)


class Snapshot(NamedTuple):
    """Where a search stood before the chunk that outgrew its ceiling: the
    chunk-boundary ``carry``, the event ``cursor`` that goes with it, and
    the ``peak`` the chunk reported (a lower bound on what the frontier
    needs).  What one placement hands to the next (``resume``): the
    search goes on from here, not from event 0."""
    carry: Tuple[Any, ...]
    cursor: int
    peak: int


class OneDevice:
    """Where :func:`_check` keeps a search and how it re-lays it there: the
    default device, the frontier in one piece.  ``parallel.sharded.OnMesh``
    is the other placement, the frontier divided over a mesh axis; the
    loop is the same, and reads from its placement the runner of a
    capacity (``capacity`` rows a shard), the staged stream, the carry
    resized, the pipeline's depth and the name the verdict goes out
    under; a placement that takes a search over (``resume``) also lays
    the snapshot's carry out its own way (``adopt``)."""

    analyzer = "wgl-tpu"
    shards = 1

    @property
    def lookahead(self) -> int:
        return LOOKAHEAD

    def runner(self, model: JaxModel, window: int, capacity: int,
               gwords: int, chunk: int):
        return _get_run_chunk(model, window, capacity, gwords, chunk)

    def stage(self, ev: np.ndarray):
        return jnp.asarray(ev)

    def grow(self, carry, capacity: int):
        return _grow_carry(carry, capacity)

    def shrink(self, carry, capacity: int):
        return _shrink_carry(carry, capacity)

    def polled(self, flags: np.ndarray, consumed: int) -> None:
        """An accepted poll's flags: nothing more to read on one device."""


def check(model: JaxModel, history: Optional[History] = None,
          prepared: Optional[PreparedHistory] = None,
          capacity: int = 1024, max_capacity: int = 65536,
          chunk: Optional[int] = None, max_window: int = 4096,
          explain: bool = True, cancel=None,
          witness_budget: int = WITNESS_BUDGET,
          growth: int = 4,
          snapshot: Optional[List[Snapshot]] = None) -> Dict[str, Any]:
    """Decide linearizability on device.  Retries with larger configuration
    capacity on overflow; falls back to ``valid: "unknown"`` past
    ``max_capacity``, and then leaves in ``snapshot``, where the caller
    gave a list, the :class:`Snapshot` another search may resume from.  On refutation, optionally re-derives a witness on the
    failing prefix with the CPU oracle (cheap: the prefix is exactly what the
    device already searched).

    ``chunk`` trades host polls against capacity adaptivity: per-closure sort
    cost scales with the *static* capacity, so small chunks let the driver
    escalate/relax capacity tightly around crash-bursts (and re-run less on
    overflow), while the lookahead pipeline hides the per-chunk flag
    transfer.  512 measured ~2x faster than 256 end-to-end where
    chunk-boundary polls dominate, with an *identical* capacity
    trajectory on the crash-burst benchmark — same configs explored, same
    peak.  ``chunk=None`` (the default) picks per history: coarse 1024 for
    ghost-light streams, fine 512 for ghost-heavy ones (see
    :func:`auto_chunk` for the measured rationale).  Pass chunk=256
    explicitly if adaptation matters more than polls.  Pure-throughput
    batch checking with no mid-stream adaptation (check_batch) uses its
    own batch-scaled chunks.

    ``cancel`` is an optional :class:`threading.Event` polled at chunk
    boundaries; when a competing solver already produced a definite verdict
    the driver stops dispatching and returns ``valid: "unknown"`` with
    ``cancelled: True`` (knossos.competition loser cancellation)."""
    with span("drivers.check") as sp:
        return _check(sp, OneDevice(), model, history, prepared, capacity,
                      max_capacity, chunk, max_window, explain, cancel,
                      witness_budget, growth, snapshot)


def _check(sp: span, place, model: JaxModel, history: Optional[History],
           prepared: Optional[PreparedHistory], capacity: int,
           max_capacity: int, chunk: Optional[int], max_window: int,
           explain: bool, cancel, witness_budget: int, growth: int,
           snapshot: Optional[List[Snapshot]] = None,
           resume: Optional[Snapshot] = None) -> Dict[str, Any]:
    """:func:`check` under its ``drivers.check`` span ``sp``, which closes
    with what the driver did: dispatches, speculative chunks discarded,
    grows, shrinks, budget-pause resumes, the chunks that continued from
    such a pause, the longest poll and the call's own share of
    :func:`check_stats`' four sums.  ``place`` (:class:`OneDevice`, or
    ``parallel.sharded.OnMesh`` under its ``drivers.shard`` span) says
    where the frontier lives; ``capacity`` and ``max_capacity`` are rows
    a shard, and ``n`` shards hold ``n`` times that.  With ``resume`` the
    search goes on from that snapshot, at the first rung its peak asks
    for, and what it counts (``configs-explored``, ``closure-rounds``)
    goes on with it."""
    p = prepared if prepared is not None else prepare(
        history, model, max_window=max_window)
    if chunk is None:
        chunk = auto_chunk(p, model)
    window = _round_window(p.window)
    gw = chosen_gwords(p)
    sp.set(events=len(p), chunk=chunk, window=window, gwords=gw)
    # Pad the event stream to a chunk multiple PLUS at least one
    # chunk-sized NOP cushion: progress is tracked in *event* units, and
    # the cushion guarantees that a dispatch starting anywhere before
    # ``n_events`` slices a full chunk without clamping back into (and
    # re-applying!) real events.  Trailing NOPs are inert.  The staged
    # array's length is part of the runner's compiled shape (the runner
    # slices it), so it goes up to the event ladder's next power of two:
    # histories of any length share a few programs, not one each.
    # ``n_events`` stays the chunk multiple: small-chunk callers keep
    # their small streams, and every dispatch costs a host poll.
    base = chunk
    with span("drivers.stage") as stage:
        ev = events_array(p, base)
        n_events = ev.shape[0]
        rows = pow2_at_least(n_events + base, MIN_EVENTS_BUCKET)
        ev = np.concatenate([ev, np.zeros((rows - n_events, 10), np.int32)])
        ev[n_events:, 0] = EV_NOP
        # One host->device transfer for the whole stream; per-chunk slices
        # then happen device-side, inside the runner.  A per-chunk
        # jnp.asarray would be a blocking ~12 KB host→device transfer per
        # dispatch, which can cost more than the chunk's compute on an
        # easy history.
        ev_dev = place.stage(ev)
        stage.set(bytes=ev.nbytes)

    n = place.shards
    cap = capacity
    if resume is not None:
        while cap < max_capacity and cap * n < 2 * resume.peak:
            cap = min(cap * growth, max_capacity)
    max_cap_reached = cap  # diagnostics: how far escalation actually went
    # The chunk is capacity-INVARIANT (see chunk_for_capacity): capacity
    # changes rebuild the engine but keep the dispatch granularity, and
    # watchdog bounding comes from the closure work budget + mid-chunk
    # resume, not from shrinking chunks.
    cur_chunk = chunk_for_capacity(cap, chunk)
    # (peak, events-consumed) samples since the last capacity change.  With
    # budget pauses a dispatch can cover anywhere from 0 to cur_chunk
    # events, so shrink-back decisions weigh samples by the events they
    # cover (>= SHRINK_WINDOW events of evidence), not by dispatch count.
    SHRINK_WINDOW = 4 * cur_chunk
    recent_peaks: deque = deque()
    # Pipelined dispatch: keep LOOKAHEAD chunks in flight so the
    # device→host flags transfer of chunk i overlaps with the device
    # computing chunk i+1.  The position in the stream is a device scalar
    # that travels with the carry (``cursor``; see _get_run_chunk), so a
    # chunk enqueued behind its predecessor starts where that one really
    # stopped: after a full chunk, or at the RETURN where the closure
    # budget paused it, which it then resumes with a fresh budget.  A
    # pause so costs one poll, overlapped like any other, and no device
    # work.  Speculation is safe: once the failed/overflow lane is set,
    # event_step gates all updates, so speculative chunks past a failure
    # compute nothing wrong — they are dropped (``discard``), as are those
    # in flight when the capacity changes.
    # (carry and cursor before the chunk, the same after it, flags)
    inflight: deque = deque()
    # Events the accepted polls consumed: the cursor of the oldest chunk
    # in flight, which is all the host knows of the position.  A chunk in
    # flight consumes at most cur_chunk, so a dispatch behind ``known +
    # len(inflight) * cur_chunk < n_events`` starts before n_events and
    # its slice ends inside the cushion.
    known = 0 if resume is None else int(resume.cursor)
    paused = False  # the last accepted poll had stopped short of its chunk
    # what the drivers.check span closes with
    did = {"dispatches": 0, "discarded": 0, "grows": 0, "shrinks": 0,
           "resumes": 0, "continued": 0, "poll_max_s": 0.0, **_zero_stats()}

    def discard(why: str) -> None:
        """Drop the speculative chunks in flight: device work done for
        nothing, one ``drivers.discard`` instant each."""
        for _ in inflight:
            instant("drivers.discard", why=why)
        did["discarded"] += len(inflight)
        inflight.clear()

    # One drivers.rung span per run of dispatches at one capacity; it holds
    # the engine lookup and the carry's resize that opened it.
    rung = span("drivers.rung", cap=cap).__enter__()

    def change_rung(why: str) -> None:
        """``cap`` changed: drop what is in flight and its evidence, and
        open the next ``drivers.rung`` with that capacity's engine."""
        nonlocal rung, run
        recent_peaks.clear()
        discard(why)
        rung.__exit__(None, None, None)
        rung = span("drivers.rung", cap=cap).__enter__()
        _, run = place.runner(model, window, cap, gw, cur_chunk)

    try:
        carry0, run = place.runner(model, window, cap, gw, cur_chunk)
        if resume is None:
            carry, cursor = carry0(), np.int32(0)
        else:
            carry = place.adopt(resume.carry, cap)
            cursor = np.int32(resume.cursor)
        # n_events >= chunk always, so the loop pops at least once and
        # failed/overflow/done are always (re)assigned before use below.
        while True:
            # Poll cancellation before refilling the pipeline, so a lost
            # race doesn't dispatch up to LOOKAHEAD more chunks of
            # discarded work.
            if cancel is not None and cancel.is_set():
                discard("cancel")
                return {"valid": "unknown", "analyzer": place.analyzer,
                        "cancelled": True}
            while len(inflight) < place.lookahead:
                # where the next chunk starts at the latest (its cursor is
                # the device's to know until the chunks before it are polled)
                latest = known + len(inflight) * cur_chunk
                if latest >= n_events:
                    break
                prev = (carry, cursor)
                with span("drivers.dispatch", pos=latest):
                    carry, cursor, flags = run(carry, cursor, ev_dev)
                did["dispatches"] += 1
                inflight.append((prev, (carry, cursor), flags))
            if not inflight:
                break
            prev, after, flags = inflight.popleft()
            with span("drivers.poll") as poll:
                fl = np.asarray(flags)
                failed, overflow = bool(fl[0]), bool(fl[1])
                peak = int(fl[2])
                consumed = int(fl[3])
                poll.set(pos=known, cap=cap, peak=peak, consumed=consumed,
                         overflow=overflow)
            did["poll_max_s"] = max(did["poll_max_s"], poll.dur_s)
            if overflow and cap < max_capacity:
                # Grow straight to a capacity the observed peak says is
                # enough (peak is a lower bound on the true need — it may
                # itself have been clipped — so the loop can escalate
                # again) and resume from the snapshot, carry and cursor:
                # no restart, no re-search of the prefix.
                while cap < max_capacity and cap * n < 2 * peak:
                    cap = min(cap * growth, max_capacity)
                max_cap_reached = max(max_cap_reached, cap)
                did["grows"] += 1
                change_rung("grow")
                carry, cursor = place.grow(prev[0], cap), prev[1]
                overflow = False
                continue
            if overflow and snapshot is not None:
                snapshot.append(Snapshot(prev[0], known, peak))
            done = after[0]
            known += consumed
            did["continued"] += paused
            did["events_consumed"] += consumed
            did["events_consumed_16k"] += \
                consumed if cap * n >= TOP_RUNG else 0
            did["cap_events"] += cap * n * consumed
            did["peak_events"] += peak * consumed
            place.polled(fl, consumed)
            if failed or overflow:
                discard("stop")
                break
            # Closure budget exhausted mid-chunk: the unconsumed tail was
            # gated to no-ops.  Nothing to repair: the chunk in flight
            # behind this one took the paused carry and its cursor, and
            # went on at the pause.  (The budget keeps one XLA program's
            # wall time bounded by work, under the TPU worker's watchdog,
            # regardless of config-count superlinearity.)
            paused = consumed < cur_chunk
            did["resumes"] += paused
            recent_peaks.append((peak, consumed))
            covered = sum(e for _, e in recent_peaks)
            while len(recent_peaks) > 1 and \
                    covered - recent_peaks[0][1] >= SHRINK_WINDOW:
                covered -= recent_peaks.popleft()[1]
            if cap > capacity and covered >= SHRINK_WINDOW:
                # Crash-bursts inflate the configuration set transiently.
                # The per-round sort cost scales with the *static*
                # capacity, so once recent peaks show a smaller buffer
                # suffices (2x headroom over the last SHRINK_WINDOW
                # events' high-water mark), drop back to a
                # cheaper-per-round engine (discarding speculative chunks).
                need = 2 * max(pk for pk, _ in recent_peaks)
                target = cap
                while target > capacity and target // growth * n >= need:
                    target //= growth
                # an escalation clamped to max_capacity can sit off the
                # power-of-4 lattice; never shrink below the configured
                # floor
                target = max(target, capacity)
                if target < cap:
                    cap = target
                    did["shrinks"] += 1
                    change_rung("shrink")
                    carry, cursor = place.shrink(after[0], cap), after[1]
        carry = done
        explored = int(carry[9])
    finally:
        rung.__exit__(None, None, None)
        sp.set(max_capacity=max_cap_reached, **did)
        with _STATS_LOCK:
            for k in _STATS:
                _STATS[k] += did[k]

    shards = {"shards": n} if n > 1 else {}
    if overflow:
        # ``explored`` only accumulates at converged RETURN prunes; a
        # history that overflows before any return prunes (the ceiling
        # shape: one giant ghost-burst closure) would report 0 even though
        # the engine explored a full frontier per closure round.  Count the
        # in-progress (clipped) frontier — its high-water mark — as
        # explored work so the overflow artifact shows what the engine did
        # before degrading.
        # "capacity-exceeded" is the structured form of the error string:
        # the fission layer keys its split-don't-escalate decision on it
        # instead of parsing the message.
        return {"valid": "unknown", "analyzer": place.analyzer,
                "error": "configuration capacity exceeded at "
                         + (f"{cap}" if n == 1 else f"{cap}x{n}"),
                "capacity-exceeded": True,
                "configs-explored": explored + int(carry[11]),
                "closure-rounds": int(carry[10]),
                "max-capacity-reached": max_cap_reached * n, **shards}
    if not failed:
        return {"valid": True, "analyzer": place.analyzer,
                "configs-explored": explored,
                "closure-rounds": int(carry[10]),
                "window": p.window, "capacity": cap * n,
                "max-capacity-reached": max_cap_reached * n, **shards}
    failed_op = p.ops[int(carry[7])]
    # (over every shard: the prune's survivors are psum'd)
    # witness: device frontier emptied on a RETURN; refuting op attached
    res: Dict[str, Any] = {"valid": False, "analyzer": place.analyzer,
                           "op": failed_op.to_dict(),
                           "configs-explored": explored,
                           "window": p.window, "capacity": cap * n,
                           "max-capacity-reached": max_cap_reached * n,
                           **shards}
    if explain and history is not None and model.cpu_model is not None:
        res["witness"] = _cpu_witness(model, history, failed_op,
                                      witness_budget)
    return res


def _grow_carry(carry, new_capacity: int):
    """Pad the configuration buffers (mask, states, valid, cur_new) of a
    chunk-boundary carry up to a larger capacity; other elements carry over.
    Gaps are fine — the engine tracks liveness with the valid flags."""
    mask, states, valid, cur_new = carry[0], carry[1], carry[2], carry[17]
    c = mask.shape[0]
    extra = new_capacity - c
    mask2 = jnp.concatenate([mask, jnp.zeros((extra,) + mask.shape[1:],
                                             mask.dtype)])
    states2 = jnp.concatenate([states, jnp.zeros((extra,) + states.shape[1:],
                                                 states.dtype)])
    valid2 = jnp.concatenate([valid, jnp.zeros(extra, valid.dtype)])
    cur_new2 = jnp.concatenate([cur_new, jnp.zeros(extra, cur_new.dtype)])
    return (mask2, states2, valid2) + tuple(carry[3:17]) + (cur_new2,)


def _shrink_carry(carry, new_capacity: int):
    """Compact live configurations into a smaller buffer (host-side; the
    arrays are KBs).  Only called when they provably fit."""
    mask = np.asarray(carry[0])
    states = np.asarray(carry[1])
    valid = np.asarray(carry[2])
    cur_new = np.asarray(carry[17])
    idx = np.flatnonzero(valid)[:new_capacity]
    mask2 = np.zeros((new_capacity,) + mask.shape[1:], mask.dtype)
    states2 = np.zeros((new_capacity,) + states.shape[1:], states.dtype)
    valid2 = np.zeros(new_capacity, bool)
    cur_new2 = np.zeros(new_capacity, bool)
    mask2[:len(idx)] = mask[idx]
    states2[:len(idx)] = states[idx]
    valid2[:len(idx)] = True
    cur_new2[:len(idx)] = cur_new[idx]
    return (jnp.asarray(mask2), jnp.asarray(states2),
            jnp.asarray(valid2)) + tuple(carry[3:17]) \
        + (jnp.asarray(cur_new2),)


# _cpu_witness / WITNESS_BUDGET / _round_window moved to the shared
# engine substrate (engine.witness, engine.ladder); imported above under
# their historical names for this module's callers.
