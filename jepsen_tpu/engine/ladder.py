"""The engine shape ladder: every compiled shape derives from pow2 buckets.

Every distinct (window, events-chunk, lane-count) triple the wgl engine
sees — and every (n_pad, lane-count) the elle closure kernel sees — is a
fresh XLA trace + compile.  Histories vary continuously in length and
concurrency, so without bucketing the engine cache would see an
unbounded stream of near-miss shapes and the device would spend its life
compiling.

The ladder is coarse on purpose: power-of-two event counts, power-of-two
width/adjacency buckets, power-of-two lane groups.  Padding waste is
bounded by 2x per axis (and measured: the scheduler reports lane
occupancy through the metrics endpoint), while the shape universe
collapses to a few dozen buckets that the bounded engine LRU
(:mod:`jepsen_tpu.engine.cache`) keeps resident.

This module is the one place a rung is defined: the bucket derivations
(history -> bucket) and the engine-side half (a set of prepared
histories plus a bucket floor -> the one shared engine shape a dispatch
compiles for), so the batch driver, the serve scheduler, and the
trace-tier lint all read the same derivation.

Discipline (enforced by SHAPE01 at call sites and TRACE02 end-to-end):
every component of an engine cache key (window, capacity, chunk, lane
pad, gwords) must be a pure function of the bucket, never of a raw
history shape — one raw ``len(h)`` leaking in reopens the unbounded
compile cache the ladder exists to close.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

from jepsen_tpu.history import FAIL, History, INVOKE, NEMESIS, OK

#: floor of the event-count ladder (matches the engine's 64-row chunking)
MIN_EVENTS_BUCKET = 64
#: floor of the wgl window ladder (engine windows are >= 8 anyway)
MIN_WIDTH_BUCKET = 8
#: floor of the elle adjacency ladder (graphs.padded_n rounds to >= 32)
MIN_N_BUCKET = 32
#: lanes per dispatch are padded to a power of two up to this cap; beyond
#: it groups dispatch at the cap exactly (parallel.batch groups at 512
#: internally anyway)
MAX_LANE_BUCKET = 512


def pow2_at_least(n: int, floor: int) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def events_bucket(h: History) -> int:
    return pow2_at_least(len(h), MIN_EVENTS_BUCKET)


def width_bucket(h: History) -> int:
    """Bucketed upper bound on the wgl engine window: the maximum number
    of simultaneously-open client ops (crashed ops never close — they hold
    window slots forever, exactly like the engine's ghost slots)."""
    open_ = 0
    peak = 1
    for op in h:
        if op.process == NEMESIS:
            continue
        if op.type == INVOKE:
            open_ += 1
            peak = max(peak, open_)
        elif op.type in (OK, FAIL):
            open_ = max(0, open_ - 1)
        # INFO: crashed — stays open
    return pow2_at_least(peak, MIN_WIDTH_BUCKET)


def elle_n_bucket(h: History) -> int:
    """Bucketed upper bound on the elle adjacency dimension: committed +
    indeterminate txns (encode keeps ok and info txns as graph nodes)."""
    n = sum(1 for op in h if op.type != INVOKE and op.process != NEMESIS)
    return pow2_at_least(max(1, n), MIN_N_BUCKET)


def lane_bucket(n_lanes: int, cap: int = MAX_LANE_BUCKET) -> int:
    """Lanes per dispatch, padded to a power of two (stable ``bpad`` in
    the engine cache key) and clamped to ``cap``."""
    return min(pow2_at_least(max(1, n_lanes), 1), cap)


#: ceiling of the megabatch lane-count ladder: concurrently-resident
#: device lanes across a bucket's groups.  Lanes beyond MAX_LANE_BUCKET
#: run as grouped vmaps of <= MAX_LANE_BUCKET width that reuse ONE
#: compiled executable (the same engine-cache entry; reuse shows up as
#: the cache's ``group_reuses`` counter) — the vmap width never grows
#: past the 512-lane bool-scatter cliff documented in parallel.batch.
MAX_MEGA_LANES = 4096

#: event buckets at or below this route through the megabatch refill
#: path when it is enabled — the "small-history path" whose steady-state
#: traffic is thousands of short per-key cells.  Larger buckets keep the
#: barrier path: their lanes are few and long, so refill wins nothing.
MEGA_EVENTS_MAX = 1024


def mega_lane_bucket(n_lanes: int, cap: int = MAX_MEGA_LANES) -> int:
    """Concurrently-resident lanes for the megabatch path: a power of
    two up to :data:`MAX_MEGA_LANES` (>= 512 means multiple grouped
    vmaps sharing one executable).  Same ladder discipline as
    :func:`lane_bucket`, one rung higher."""
    return min(pow2_at_least(max(1, n_lanes), 1), cap)


#: floor of the model state-width ladder: packed per-configuration model
#: states (register scalars, queue rings, set bitmask words, txn-register
#: key vectors) quantize onto pow2 widths starting here, so the carry
#: layout the megabatch path compiles for is a pure function of the
#: bucket — a queue sized by ``derive_queue_slots`` and a bare register
#: land on the SAME finite rung set.
MIN_STATE_WIDTH_BUCKET = 4


def state_width_bucket(state_width: int) -> int:
    """The pow2 rung for a model's packed int32 state width (the
    ``JaxModel.state_size`` axis of the megabatch carry).  Model sizing
    hooks (``derive_queue_slots`` etc.) already emit pow2 sizes, so this
    collapses the per-model width spread onto a handful of rungs shared
    by every model family — the state axis of the bounded shape universe
    megabatch and ``check_batch`` dispatch from."""
    return pow2_at_least(max(1, state_width), MIN_STATE_WIDTH_BUCKET)


#: floor / ceiling of the derived wgl start-capacity ladder
MIN_WGL_CAPACITY = 64
MAX_WGL_CAPACITY = 65536


def wgl_start_capacity(ev_bucket: int, w_bucket: int) -> int:
    """Derive the wgl engine's *starting* configuration capacity from the
    bucket shape instead of a fixed knob.

    The config frontier is bounded by (subsets of the pending window) x
    (reachable model states); in practice it tracks the window width far
    more than history length, so the ladder is quadratic in the width
    bucket (w=8 -> 256, the old fixed default; w=16 -> 1024; w=32 ->
    4096), hard-capped by both 2**w (the true subset bound for small
    windows) and :data:`MAX_WGL_CAPACITY`.  Longer event streams do not
    widen the frontier per step, so ``ev_bucket`` only nudges the floor
    up for big histories (avoids one guaranteed escalation round-trip on
    multi-thousand-op cells).

    Crucially this is a pure function of the (ev, w) bucket, so the
    derived capacity is constant per bucket and the compiled-engine
    cache key stays stable — deriving from raw history shape would leak
    the unbounded shape universe right back into the cache.

    The ``JEPSEN_TPU_WGL_CAPACITY`` env var overrides the derivation
    (resolved by the scheduler, not here), and per-request ``capacity``
    engine opts override both.
    """
    cap = pow2_at_least(4 * w_bucket * w_bucket, MIN_WGL_CAPACITY)
    if ev_bucket >= 4096:
        cap *= 2
    if w_bucket < 16:
        cap = min(cap, 2 ** w_bucket)
    return max(MIN_WGL_CAPACITY, min(cap, MAX_WGL_CAPACITY))


#: floor / ceiling of the streaming monitor's per-epoch dispatch ladder.
#: A monitored stream's epoch delivers a raw new-op count that varies
#: continuously; the device-resident frontier (engine/stream.py) pads each
#: epoch's event rows onto this pow2 ladder so the compiled epoch-advance
#: executable is keyed on a handful of chunk rungs, not on raw epoch sizes.
#: The ceiling keeps one epoch dispatch's scan bounded — a larger backlog
#: simply dispatches several ceiling-sized chunks.
MIN_EPOCH_EVENTS_BUCKET = 64
MAX_EPOCH_EVENTS_BUCKET = 2048


def epoch_events_bucket(n_new: int) -> int:
    """The stream engine's per-epoch event-chunk rung: pow2 at least the
    new-op count, clamped to [MIN_EPOCH_EVENTS_BUCKET,
    MAX_EPOCH_EVENTS_BUCKET].  Pure function of the new-op count alone —
    total history length must never reach an epoch dispatch shape, or the
    compiled-signature universe grows with stream lifetime (the exact
    leak TRACE02's stream leg guards)."""
    return min(pow2_at_least(max(1, n_new), MIN_EPOCH_EVENTS_BUCKET),
               MAX_EPOCH_EVENTS_BUCKET)


def wgl_bucket(h: History) -> Tuple[int, int]:
    return (events_bucket(h), width_bucket(h))


def elle_bucket(h: History) -> Tuple[int]:
    return (elle_n_bucket(h),)


#: Target lane-events per dispatch: the vmapped scan costs ~(batch x
#: chunk) lane-event steps, so the chunk shrinks as the batch grows to
#: keep one XLA program's duration roughly constant regardless of batch
#: size.
LANE_EVENTS_PER_DISPATCH = 16384


def round_window(w: int) -> int:
    """Tightest engine window for a history: multiple of 4, >= 8."""
    return max(8, ((w + 3) // 4) * 4)


def batch_chunk(bpad: int, longest: int) -> int:
    """Events per dispatch for a ``bpad``-lane batch (multiple of 64,
    clamped to [64, 2048] and to the longest lane rounded up)."""
    c = max(64, min(2048, (LANE_EVENTS_PER_DISPATCH // max(1, bpad))
                    // 64 * 64))
    return min(c, max(64, ((longest + 63) // 64) * 64))


def batch_shape(preps: Sequence, window_floor: int = 0) -> Tuple[int, int, int]:
    """The one shared wgl engine shape for a batch of prepared histories:
    ``(window, gwords, longest)``.

    All lanes share one engine shape — window = max over histories
    (rounded onto the window ladder, floored by the caller's bucket),
    ghost words = max over lanes (lean gwords=0 only when EVERY lane
    qualifies: the shape is shared, and a non-qualifying lane's
    ghost_words dominates the max anyway)."""
    from jepsen_tpu.checker.wgl_tpu import chosen_gwords
    window = round_window(max(window_floor, max(p.window for p in preps)))
    gwords = max(chosen_gwords(p) for p in preps)
    longest = max(len(p) for p in preps)
    return window, gwords, longest


def pad_words(n: int, word: int = 32) -> int:
    """Round ``n`` up to a whole number of ``word``-sized words.  The one
    word-padding derivation in the stack: the elle adjacency pad
    (``elle_tpu``'s 32-row closure tiles) and any packed-bitmask state
    sizing round here instead of keeping private ``(n + 31) // 32 * 32``
    copies."""
    return ((max(0, n) + word - 1) // word) * word


def _state_halvings(state_width: int) -> int:
    """Rungs the state-width bucket sits above the register floor — the
    damping exponent shared by :func:`mega_chunk` and
    :func:`state_capacity`."""
    sw_bucket = state_width_bucket(state_width)
    return max(0, sw_bucket.bit_length()
               - MIN_STATE_WIDTH_BUCKET.bit_length())


def mega_chunk(bpad: int, longest: int, state_width: int) -> int:
    """Events per dispatch for a megabatch lane group, state-width
    aware: start from :func:`batch_chunk` and halve once per rung the
    model's packed state sits above the register floor (a queue ring or
    txn key vector multiplies the per-step merge cost by its width, so
    wide-state dispatches shorten to keep one XLA program's duration
    roughly constant).  Still a multiple of 64 with floor 64, and still
    a pure function of (lane bucket, events bucket, state-width bucket)
    — the raw ``state_width`` is quantized internally, so equal buckets
    always derive equal chunks."""
    c = batch_chunk(bpad, longest)
    c = (c >> _state_halvings(state_width)) // 64 * 64
    return max(64, c)


def state_capacity(ev_bucket: int, w_bucket: int, state_width: int) -> int:
    """The wgl *starting* capacity for a model with a ``state_width``-wide
    packed state: :func:`wgl_start_capacity`
    shifted down one rung per state-width doubling past the register
    floor.  Wide states make each resident configuration proportionally
    more expensive (memory and merge cost both scale with the packed
    width), and under-starting is safe — overflow lanes escalate up the
    :func:`next_capacity` ladder — so the derivation trades a possible
    escalation round-trip for not compiling huge frontiers nobody needs.
    Pure function of the (ev, w, state-width) bucket triple; floored at
    ``MIN_WGL_CAPACITY``."""
    cap = wgl_start_capacity(ev_bucket, w_bucket)
    return max(MIN_WGL_CAPACITY, cap >> _state_halvings(state_width))


def next_capacity(cap: int, max_capacity: int, growth: int = 8) -> Optional[int]:
    """The next rung of the capacity-escalation ladder, or None when
    ``cap`` already hit the ceiling (the caller degrades the remaining
    lanes to ``unknown`` — never to false)."""
    if cap >= max_capacity:
        return None
    return min(cap * growth, max_capacity)
