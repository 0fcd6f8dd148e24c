"""Megabatch: the batch-throughput path for thousands of small histories.

``check_batch`` treats a batch as one barrier: every lane is padded to
the batch max shape, every dispatch transfers a ``[lanes, 5]`` flag
array back to the host, and a batch does not finish until its slowest
lane does.  That is the wrong shape for the serving fleet, whose
steady-state traffic is thousands of SHORT per-key histories (the
product of P-compositional decomposition): the device spends its time
waiting on per-dispatch host polls and on retired lanes idling inside a
barrier.

This module keeps the device saturated instead:

* **Bucket bin-packing.**  Prepared histories are packed into the
  power-of-two bucket ladder (events x window x ghost-words x
  state-width, the ladder ``engine/ladder.py`` pins the compile cache
  to), so one compiled engine serves every lane of a bucket and the
  shape universe stays bounded.
* **Model-agnostic carries.**  The engine carry layout is the same for
  every device model — only the packed ``states`` width varies — so any
  model family with a registered carry descriptor
  (``engine.plugins.has_carry_descriptor``; the
  ``JaxModel.carry_descriptor()`` shape+dtype seam) bin-packs into this
  loop: queue rings, set bitmasks, and txn-register key vectors ride
  the same dispatch machinery as registers, with chunk and start
  capacity damped per state-width rung (``engine.ladder.mega_chunk`` /
  ``state_capacity``).
* **Contiguous staging + double-buffered transfer.**  Each lane group's
  event streams live in ONE contiguous pinned host buffer; refills
  rewrite rows host-side and re-upload with an async ``device_put``
  that overlaps the in-flight scan (JAX async dispatch) — the host
  never calls ``block_until_ready`` between dispatches.
* **Fused O(1) readback.**  The per-dispatch verdict reduction runs
  inside the jitted step: each dispatch returns a single
  ``int32[SUMMARY_WIDTH]`` vector per group (live/done/failed/overflow
  counts), not per-lane arrays.  Per-lane results are read only at
  harvest points (a retire/refill event), amortized over many
  dispatches.
* **Continuous lane refill.**  Lanes that finish early retire and are
  backfilled from the staging queue inside the jitted ``reset`` (a
  masked select against the initial carry) — no batch barriers.
* **Donated carries.**  The per-chunk carry is donated
  (``donate_argnums``) on non-CPU backends so XLA updates it in place
  (see parallel.batch.donate_carry_argnums).

Overflowing lanes retire with a sentinel and are re-checked through
plain :func:`jepsen_tpu.parallel.batch.check_batch` at escalated
capacity after the megabatch drains — capacity only affects overflow,
never verdicts, so results are identical to the barrier path lane for
lane.

Host↔device traffic discipline is observable: every device→host read
on this path goes through one counted chokepoint (`megabatch_stats`),
and ``transfer_guard=True`` additionally arms JAX's transfer guard so
an uncounted transfer raises instead of silently costing a sync.
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict, deque
from contextlib import contextmanager, nullcontext
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jepsen_tpu.checker.prep import prepare
from jepsen_tpu.checker.wgl_tpu import (EV_NOP, _round_window, chosen_gwords,
                                        events_array, make_engine)
from jepsen_tpu.engine.ladder import (mega_chunk, pow2_at_least,
                                      state_capacity, state_width_bucket)
from jepsen_tpu.history import History
from jepsen_tpu.models.base import JaxModel
from jepsen_tpu.parallel.batch import (MAX_LANES_PER_GROUP, _CACHE,
                                       check_batch, donate_carry_argnums)

__all__ = ["check_megabatch", "megabatch_enabled", "megabatch_stats",
           "reset_megabatch_stats", "SUMMARY_WIDTH"]

#: ints per per-dispatch summary readback: live, done, failed, overflow
#: lane counts over the group.  O(1) — independent of the lane count.
SUMMARY_WIDTH = 4

#: ints per lane in a harvest readback: status, failed_op, explored,
#: consumed.  Status codes below.
HARVEST_WIDTH = 4
STATUS_RUNNING = 0   # still live (or an empty pad lane)
STATUS_VALID = 1
STATUS_FAILED = 2
STATUS_OVERFLOW = 3

#: default cap on concurrently-resident lanes (across a bucket's groups);
#: the lane-count ladder (``engine.ladder.mega_lane_bucket``) feeds
#: this from the scheduler side.
DEFAULT_MAX_LANES = 4096


def megabatch_enabled() -> bool:
    """The ``JEPSEN_TPU_MEGABATCH`` kill switch (default: enabled)."""
    return os.environ.get("JEPSEN_TPU_MEGABATCH", "1").lower() \
        not in ("0", "false", "no", "off")


def staging_depth_default() -> int:
    """In-flight dispatches per group (``JEPSEN_TPU_STAGING_DEPTH``).

    Depth 2 is the classic double-buffer: while the host blocks on
    dispatch N's summary, dispatch N+1 is already queued on the device.
    """
    try:
        return max(1, int(os.environ.get("JEPSEN_TPU_STAGING_DEPTH", "2")))
    except ValueError:
        return 2


# ---------------------------------------------------------------------------
# Readback accounting
# ---------------------------------------------------------------------------

_STATS_LOCK = threading.Lock()


def _zero_stats() -> Dict[str, int]:
    return {"calls": 0, "staged_lanes": 0, "buckets": 0, "groups": 0,
            "dispatches": 0, "summary_reads": 0, "summary_ints": 0,
            "harvests": 0, "harvest_ints": 0, "refills": 0,
            "lanes_refilled": 0, "lanes_retired": 0, "escalated_lanes": 0}


_STATS = _zero_stats()


def megabatch_stats() -> Dict[str, int]:
    """Counters over every megabatch run in this process.  The O(1)
    readback invariant is checkable from the outside: per-dispatch reads
    are ``summary_ints == summary_reads * SUMMARY_WIDTH`` with
    ``summary_reads <= dispatches`` (a harvest discards its group's
    unread in-flight summaries), and every other device→host read is a
    (rare, refill-amortized) harvest."""
    with _STATS_LOCK:
        return dict(_STATS)


def reset_megabatch_stats() -> None:
    with _STATS_LOCK:
        _STATS.update(_zero_stats())


def _bump(**kw: int) -> None:
    with _STATS_LOCK:
        for k, v in kw.items():
            _STATS[k] += v


@contextmanager
def _allow_d2h():
    """Readback chokepoint escape hatch for the armed transfer guard."""
    with jax.transfer_guard_device_to_host("allow"):
        yield


def _read_summary(dev) -> np.ndarray:
    with _allow_d2h():
        a = np.asarray(dev)
    _bump(summary_reads=1, summary_ints=int(a.size))
    return a


def _read_harvest(dev) -> np.ndarray:
    from jepsen_tpu.obs.recorder import RECORDER
    t0 = time.monotonic()
    with _allow_d2h():
        a = np.asarray(dev)
    _bump(harvests=1, harvest_ints=int(a.size))
    RECORDER.record("transfer", "d2h:harvest",
                    dur_s=time.monotonic() - t0,
                    args={"ints": int(a.size)})
    return a


# ---------------------------------------------------------------------------
# Bucketing (the engine.ladder rungs the compile cache is pinned to)
# ---------------------------------------------------------------------------

def _prep_bucket(p, window_floor: int, ev_floor: int, gw_b: int,
                 sw_b: int) -> Tuple[int, int, int, int]:
    """(events, window, gwords, state-width) bucket of one prepared
    history.

    Events and window are pure functions of the single history, so
    packing order and group makeup can never change the engine shape a
    lane runs under (the packing-invariance contract the tests fuzz).
    The ghost-word rung is the CALL-level pow2 ceiling (check_batch's
    "lean only when every lane qualifies" rule): an engine with at least
    a lane's chosen ghost words is result-identical for that lane
    (LEAN_GHOST_MAX=0 means lean only ever runs zero-ghost histories),
    and one shared rung keeps a mixed call in one bucket instead of
    fragmenting the lane groups on ghost count.  The state-width rung is
    the model's packed-carry width off the state-width ladder — constant
    per call (one model per call) but part of the key so the chunk and
    start-capacity derivations downstream are pure functions of the
    bucket tuple alone."""
    ev_b = pow2_at_least(max(1, len(p)), max(64, ev_floor))
    w_b = pow2_at_least(_round_window(max(p.window, window_floor)), 8)
    return (ev_b, w_b, gw_b, sw_b)


def _call_gwords(preps) -> int:
    gw = max(chosen_gwords(p) for p in preps)
    return 0 if gw == 0 else pow2_at_least(gw, 1)


# ---------------------------------------------------------------------------
# The jitted group programs (cached in the shared engine LRU)
# ---------------------------------------------------------------------------

def _mega_runner(model: JaxModel, window: int, capacity: int, gwords: int,
                 chunk: int, width: int, group_reuse: bool = False):
    """(carry0, step, harvest, reset) for one group shape.

    ``step``   : (carry, events, lane_len) -> (carry', int32[SUMMARY_WIDTH])
                 — one vmapped single-round chunk plus the fused verdict
                 reduction; the carry is donated.
    ``harvest``: (carry, lane_len) -> int32[width, HARVEST_WIDTH]
                 — per-lane (status, failed_op, explored, consumed).
    ``reset``  : (carry, refill_mask) -> carry' with refilled lanes set
                 back to the initial engine carry; the carry is donated.
    """
    key = ("megav", model.name, model.variant, model.state_size,
           tuple(model.init_state_array().tolist()), window, capacity,
           gwords, chunk, width)
    hit = _CACHE.get(key, group_reuse=group_reuse)
    if hit is not None:
        return hit

    carry0, _, run_chunk = make_engine(model, window, capacity,
                                       gwords=gwords, work_budget=0,
                                       single_round_closure=True,
                                       steps_per_dispatch=chunk)
    vrun = jax.vmap(run_chunk, in_axes=(0, 0))

    def _liveness(failed, overflow, consumed, stalled, lane_len):
        real = lane_len > 0
        live = real & ~failed & ~overflow \
            & ((consumed < lane_len) | stalled)
        done = real & ~live
        return real, live, done

    def step(carry, events, lane_len):
        carry, flags = vrun(carry, events)
        failed = flags[:, 0] != 0
        overflow = flags[:, 1] != 0
        consumed = flags[:, 3]
        stalled = flags[:, 4] != 0
        _, live, done = _liveness(failed, overflow, consumed, stalled,
                                  lane_len)
        summary = jnp.stack([
            live.sum().astype(jnp.int32),
            done.sum().astype(jnp.int32),
            (done & failed).sum().astype(jnp.int32),
            (done & overflow).sum().astype(jnp.int32),
        ])
        return carry, summary

    def harvest(carry, lane_len):
        failed = carry[6]
        overflow = carry[8]
        consumed = carry[14]
        stalled = carry[18] >= 0
        real, live, _ = _liveness(failed, overflow, consumed, stalled,
                                  lane_len)
        status = jnp.where(
            ~real | live, STATUS_RUNNING,
            jnp.where(overflow, STATUS_OVERFLOW,
                      jnp.where(failed, STATUS_FAILED, STATUS_VALID)))
        return jnp.stack([status.astype(jnp.int32),
                          carry[7].astype(jnp.int32),
                          carry[9].astype(jnp.int32),
                          consumed.astype(jnp.int32)], axis=1)

    c0 = carry0()
    c0b = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (width,) + x.shape), c0)

    def reset(carry, refill_mask):
        def sel(cur, init):
            m = refill_mask.reshape((width,) + (1,) * (cur.ndim - 1))
            return jnp.where(m, init, cur)
        return jax.tree.map(sel, carry, c0b)

    donate = donate_carry_argnums()
    from jepsen_tpu.obs.hist import timed_first_call
    step_j = timed_first_call(
        jax.jit(step, donate_argnums=donate),
        f"compile:megav:{model.name}:w{window}:c{capacity}"
        f":k{chunk}:l{width}")
    harvest_j = jax.jit(harvest)
    reset_j = jax.jit(reset, donate_argnums=donate)
    return _CACHE.put(key, (carry0, step_j, harvest_j, reset_j))


# ---------------------------------------------------------------------------
# Host-side group state
# ---------------------------------------------------------------------------

class _Group:
    """One vmapped lane group: a contiguous host staging buffer, its
    device mirror, the engine carry, and the lane→history bookkeeping."""

    def __init__(self, width: int, rows: int, carry0):
        self.width = width
        # The contiguous pinned staging buffer: all of a group's lanes in
        # one [width, rows, 10] block, so a refill's device_put is one
        # coalesced transfer instead of per-lane scatters.
        self.host_ev = np.zeros((width, rows, 10), np.int32)
        self.host_ev[:, :, 0] = EV_NOP
        self.host_len = np.zeros(width, np.int32)
        self.slots: List[Optional[int]] = [None] * width
        c0 = carry0()
        self.carry = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (width,) + x.shape), c0)
        self.ev_dev = None
        self.len_dev = None
        self.pending: "deque" = deque()     # in-flight dispatch summaries
        self.live_est = 0                   # from the last summary read
        self.expect = 0                     # dispatches this fill needs

    def load(self, lane: int, hist_idx: int, ev: np.ndarray) -> None:
        self.host_ev[lane, :, 0] = EV_NOP
        self.host_ev[lane, :, 1:] = 0
        self.host_ev[lane, :ev.shape[0]] = ev
        self.host_len[lane] = ev.shape[0]
        self.slots[lane] = hist_idx

    def upload(self) -> None:
        """Async device_put of the coalesced staging buffer — enqueued
        behind the in-flight dispatches, overlapping their compute."""
        self.ev_dev = jax.device_put(np.ascontiguousarray(self.host_ev))
        self.len_dev = jax.device_put(self.host_len.copy())


# ---------------------------------------------------------------------------
# The megabatch driver
# ---------------------------------------------------------------------------

def check_megabatch(model: JaxModel,
                    histories: Sequence[History],
                    capacity: Optional[int] = None,
                    max_capacity: int = 65536,
                    window_floor: int = 0,
                    ev_floor: int = 0,
                    lanes: int = DEFAULT_MAX_LANES,
                    chunk: Optional[int] = None,
                    staging_depth: Optional[int] = None,
                    refill_quantum: Optional[int] = None,
                    transfer_guard: bool = False) -> List[Dict[str, Any]]:
    """Check many (small) histories with continuous lane refill; returns
    one result dict per history, in input order.

    Verdicts, refuting ops, and ``configs-explored`` are identical to
    :func:`check_batch` and to the CPU oracle lane for lane, and are
    invariant under input order and group-size choices: every lane runs
    under an engine shape derived purely from its own (events, window,
    ghost-words) bucket, never from what it happens to be packed with.

    ``lanes`` caps concurrently-resident device lanes (the scheduler
    feeds it from the serve lane-count ladder); ``staging_depth`` is the
    per-group in-flight dispatch depth (default: env
    ``JEPSEN_TPU_STAGING_DEPTH`` or 2); ``refill_quantum`` is the retired
    lane count that triggers a harvest+refill (default: width // 4).
    ``transfer_guard=True`` arms JAX's device→host transfer guard outside
    the counted readback chokepoints, so any stray per-dispatch transfer
    raises loudly (the CI smoke runs with it armed).
    """
    if not histories:
        return []
    _bump(calls=1, staged_lanes=len(histories))
    depth = staging_depth if staging_depth else staging_depth_default()
    preps = [prepare(h, model) for h in histories]

    gw_b = _call_gwords(preps)
    sw_b = state_width_bucket(model.state_size)
    buckets: "OrderedDict[Tuple[int, int, int, int], List[int]]" = \
        OrderedDict()
    for i, p in enumerate(preps):
        buckets.setdefault(
            _prep_bucket(p, window_floor, ev_floor, gw_b, sw_b),
            []).append(i)

    out: List[Optional[Dict[str, Any]]] = [None] * len(histories)
    guard = jax.transfer_guard_device_to_host("disallow") \
        if transfer_guard else nullcontext()
    with guard:
        for bi, (bucket, idxs) in enumerate(buckets.items()):
            _drain_bucket(model, histories, preps, bucket, idxs, out,
                          capacity=capacity, max_capacity=max_capacity,
                          lanes=lanes, chunk=chunk, depth=depth,
                          refill_quantum=refill_quantum,
                          group_reuse=bi > 0)
    return out  # type: ignore[return-value]


def _drain_bucket(model, histories, preps, bucket, idxs, out, *,
                  capacity, max_capacity, lanes, chunk, depth,
                  refill_quantum, group_reuse) -> None:
    """Run every history of one (events, window, gwords, state-width)
    bucket through a refilled set of lane groups, writing results into
    ``out``."""
    ev_b, w_b, gw_b, sw_b = bucket
    _bump(buckets=1)
    width = min(pow2_at_least(min(len(idxs), lanes), 1),
                MAX_LANES_PER_GROUP)
    # Chunk and start capacity come off the state-width-aware ladder
    # shared with check_batch: pure functions of the bucket tuple, so a
    # queue ring and a register cell compile into the same bounded shape
    # universe (just on different state rungs).
    cc = chunk if chunk else mega_chunk(width, ev_b, sw_b)
    # Buffer rows are a pure function of the bucket (+1 trailing NOP row
    # that finished cursors clamp onto), never of the lanes present.
    rows = max(cc, ((ev_b + cc - 1) // cc) * cc) + 1
    cap = capacity if capacity else state_capacity(ev_b, w_b, sw_b)
    cap = min(cap, max_capacity)
    n_groups = max(1, min((len(idxs) + width - 1) // width,
                          max(1, lanes // width)))
    quantum = refill_quantum if refill_quantum else max(1, width // 4)
    # Dispatches a stall-free fill takes: the whole staged buffer is one
    # chunk scan per `cc` rows.  This caps the prefetch depth so the
    # pipeline never burns a full extra chunk scan on a done carry.
    exp0 = max(1, (rows - 1) // cc)

    staging = deque(idxs)
    escalate: List[int] = []

    groups: List[_Group] = []
    for g in range(n_groups):
        if not staging:
            break
        # Each group re-fetches the cached runner: the call's first fetch
        # is an ordinary hit/miss, every later group is a same-dispatch
        # executable reuse (the group_reuses counter in the engine LRU).
        carry0, step_j, harvest_j, reset_j = _mega_runner(
            model, w_b, cap, gw_b, cc, width,
            group_reuse=group_reuse or g > 0)
        grp = _Group(width, rows, carry0)
        _fill(grp, range(width), staging, preps, cc)
        grp.upload()
        grp.expect = exp0
        groups.append(grp)
    _bump(groups=len(groups))

    # Generous progress bound: every real lane finishes within
    # (window + 2) rounds per event (a pending return stalls at most
    # window + 1 closure rounds), plus slack for NOP tails and refills.
    fills = (len(idxs) + width * max(1, len(groups)) - 1) \
        // (width * max(1, len(groups))) + 1
    max_disp = 64 + 8 * fills * len(groups) * (w_b + 2) \
        * ((rows + cc - 1) // cc)

    active = list(groups)
    dispatched = 0
    while active:
        for grp in list(active):
            # Keep the pipeline as full as the remaining work plausibly
            # needs: `expect` is the stall-free dispatch count of the
            # current fill; once it is spent, lanes that are still live
            # (stalled on pending returns) get one dispatch at a time.
            # The carry chains on device; the host never blocks between
            # dispatches.
            while len(grp.pending) < depth \
                    and (grp.expect > 0 or not grp.pending):
                grp.carry, summ = step_j(grp.carry, grp.ev_dev,
                                         grp.len_dev)
                grp.pending.append(summ)
                grp.expect = max(0, grp.expect - 1)
                dispatched += 1
                _bump(dispatches=1)
            # O(1) readback: the oldest in-flight summary (4 ints).
            s = _read_summary(grp.pending.popleft())
            live, done = int(s[0]), int(s[1])
            grp.live_est = live
            if live == 0 and not staging:
                # Bucket drained through this group: final harvest.
                grp.pending.clear()
                _harvest(grp, harvest_j, preps, out, escalate, staging,
                         cc, refill=False)
                active.remove(grp)
            elif staging and (done >= min(quantum, len(staging))
                              or live == 0):
                # Early-retiring lanes: harvest the finished ones and
                # backfill from the staging queue (continuous refill).
                grp.pending.clear()
                freed = _harvest(grp, harvest_j, preps, out, escalate,
                                 staging, cc, refill=True)
                if freed:
                    reset_mask = np.zeros(grp.width, bool)
                    reset_mask[freed] = True
                    # The refilled staging buffer rides up on an async
                    # device_put that overlaps whatever compute other
                    # groups have in flight.
                    grp.upload()
                    grp.carry = reset_j(grp.carry,
                                        jax.device_put(reset_mask))
                    grp.expect = exp0
                    _bump(refills=1, lanes_refilled=len(freed))
        if dispatched > max_disp:
            raise RuntimeError(
                f"megabatch made no progress after {dispatched} dispatches "
                f"(bucket {bucket}, {len(staging)} staged remaining)")

    if escalate:
        # Overflowed lanes re-run through the barrier path at escalated
        # capacity; capacity never changes verdicts, only whether the
        # frontier fits, so parity is preserved.
        _bump(escalated_lanes=len(escalate))
        esc = check_batch(model, [histories[i] for i in escalate],
                          capacity=min(cap * 8, max_capacity),
                          max_capacity=max_capacity,
                          window_floor=w_b)
        for i, r in zip(escalate, esc):
            out[i] = r


def _fill(grp: _Group, lanes_iter, staging, preps, cc) -> None:
    """Load staged histories into free lanes of ``grp`` (host side)."""
    for lane in lanes_iter:
        if not staging:
            break
        hist_idx = staging.popleft()
        grp.load(lane, hist_idx, events_array(preps[hist_idx], cc))


def _harvest(grp: _Group, harvest_j, preps, out, escalate, staging,
             cc, refill: bool) -> List[int]:
    """Read per-lane results for finished lanes, record them, and (when
    refilling) reload the freed lanes from the staging queue.  Returns
    the refilled lane indices."""
    h = _read_harvest(harvest_j(grp.carry, grp.len_dev))
    freed: List[int] = []
    for lane in range(grp.width):
        hist_idx = grp.slots[lane]
        if hist_idx is None or h[lane, 0] == STATUS_RUNNING:
            continue
        status, failed_op, explored = (int(h[lane, 0]), int(h[lane, 1]),
                                       int(h[lane, 2]))
        if status == STATUS_OVERFLOW:
            escalate.append(hist_idx)
        elif status == STATUS_FAILED:
            # witness: the lane's frontier emptied; its refuting op rides
            out[hist_idx] = {
                "valid": False, "analyzer": "wgl-tpu-megabatch",
                "op": preps[hist_idx].ops[failed_op].to_dict(),
                "configs-explored": explored}
        else:
            out[hist_idx] = {"valid": True,
                             "analyzer": "wgl-tpu-megabatch",
                             "configs-explored": explored}
        grp.slots[lane] = None
        grp.host_len[lane] = 0
        _bump(lanes_retired=1)
        if refill and staging:
            _fill(grp, [lane], staging, preps, cc)
            freed.append(lane)
    return freed
