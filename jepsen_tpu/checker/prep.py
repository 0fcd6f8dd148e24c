"""History preprocessing for linearizability engines.

Turns a raw history into the event stream both engines (CPU oracle and TPU
search) consume, and computes the *pending-window* slot assignment that is the
core compression behind the device representation:

    In the configuration-BFS view of linearizability checking (Wing & Gong's
    search, as refined by Lowe's just-in-time linearization), a configuration
    is (set of linearized ops, model state).  But every op whose completion
    event has been processed MUST be linearized in every surviving
    configuration, and ops not yet invoked CANNOT be — so configurations can
    only disagree about ops that are *currently pending*.  A configuration
    therefore compresses to (bitmask over pending-window slots, model state):
    a handful of int32 lanes, fixed-shape, perfect for vmapped expansion on
    device.  (See PAPERS.md: P-compositionality's just-in-time linearization;
    knossos's configurations play the same role on the JVM.)

Rules applied here (knossos parity):
  - only client ops participate (nemesis ops are stripped);
  - ``fail`` ops never took effect — invoke+fail pairs are removed outright;
  - ``info`` ops may take effect at any time from invocation on — they enter
    the window and never leave (crashed ops, reference behavior at
    jepsen/src/jepsen/generator/interpreter.clj:142-157);
  - ``info`` pure-read ops with unknown values are dropped (unconstraining);
  - ``ok`` ops produce an ENTER event at their invocation index and a RETURN
    event at their completion index.

What a call costs.  Two passes over the history it is handed and nothing
kept between calls: one that pairs invokes with completions by process
(``History.pair_index``'s rule, nemesis ops skipped in place), one that emits
the events; no intermediate ``History``, and an ``Op`` is copied only where
its ok completion brings a value the invoke did not carry (a read).  1.5 us
an entry on the benchmark machine's host (``prepare.s_per_kop.keyed`` 0.0015
s per 1,000 entries, 0.47 s of a 2.07 s ``keyed200.offline`` call for its 512
lanes; the three-pass form before it: 3.0 us, 0.97 s.  PERF.md section 6,
my chip runs, PR 36), 1.3 us in the sandbox: the pairing pass a third, the
event pass with ``encode_op`` and the copies a half, ``np.array`` a sixth.
``tests/test_prep.py`` keeps the three-pass form as the reference.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Set, Tuple

import numpy as np

from jepsen_tpu.history import (
    FAIL, History, INFO, INVOKE, NEMESIS, OK, Op,
)
from jepsen_tpu.models.base import JaxModel, UNKNOWN32
from jepsen_tpu.obs.recorder import span

EV_ENTER = 0   # op joins the pending window (its invocation)
EV_RETURN = 1  # op's ok-completion: must be linearized in every config


@dataclass
class PreparedHistory:
    """Event-stream view of a history, ready for either engine."""

    # Per-event columns (length E):
    kind: np.ndarray        # int32, EV_ENTER / EV_RETURN
    slot: np.ndarray        # int32, pending-window slot of the event's op
    f: np.ndarray           # int32, model op code (0 if no encoder given)
    a: np.ndarray           # int32 operand
    b: np.ndarray           # int32 operand
    op_id: np.ndarray       # int32, index into ``ops`` (invocation order)
    ghost: np.ndarray       # int32 0/1: ENTER of an op that never returns
                            # (info/crashed) — enables ghost-bit subsumption
    gcls: np.ndarray        # int32: ghost equivalence class (slot of the
                            # first ghost with the same (f,a,b) encoding);
                            # -1 for non-ghost events.  Same-encoding ghosts
                            # are interchangeable, so engines canonicalize
                            # a config's ghost bits to per-class counts.
    grank: np.ndarray       # int32: this ghost's index within its class
    gpos: np.ndarray        # int32: compact ghost bit position, grouped by
                            # class (class offset + rank) — ghost state
                            # packs into ceil(n_ghosts/32) sort words
                            # instead of ceil(window/32)
    # Scalars / host-side:
    window: int             # number of slots ever needed (max concurrency)
    ops: List[Op]           # participating ops, invocation order
    crashed_slots: Tuple[int, ...]  # slots held forever by info ops
    n_ghosts: int = 0       # total crashed ops (= compact ghost bits)

    @property
    def n_ops(self) -> int:
        return len(self.ops)

    def __len__(self):
        return len(self.kind)


class WindowOverflow(Exception):
    """History's pending-op concurrency exceeds the engine's window size."""


def prepare(history: History,
            model: Optional[JaxModel] = None,
            max_window: Optional[int] = None,
            pure_read_names: Sequence[str] = ("read", "r"),
            ) -> PreparedHistory:
    """Build the event stream.  With a :class:`JaxModel`, ops are encoded into
    the int32 (f, a, b) columns and the model's ``pure_read_fs`` drive
    crashed-read elimination; without one (host-tier engines), columns are
    zero and ``pure_read_names`` + a None value identify droppable reads."""
    with span("prepare", entries=len(history)):
        return _prepare(history, model, max_window, pure_read_names)


def _prepare(history: History, model: Optional[JaxModel],
             max_window: Optional[int], pure_read_names: Sequence[str],
             ) -> PreparedHistory:
    # Pairing pass (History.pair_index's rule, by process, nemesis ops
    # skipped in place).  One record an invoke: [op, completion, slot,
    # op id].  ``order`` holds a record at its invoke and again at its ok
    # completion; a fail or info completion only fills the record.
    order: List[list] = []
    open_invokes: dict = {}     # process -> record of its open invoke
    n_nemesis = 0
    for i, op in enumerate(history.ops):
        p = op.process
        if p == NEMESIS:
            n_nemesis += 1
            continue
        t = op.type
        if t == INVOKE:
            if op.index is None:
                # History.__init__ parity: position in the client view
                op = op.with_(index=i - n_nemesis)
            rec = open_invokes[p] = [op, None, -1, 0]
            order.append(rec)
        elif t == OK or t == FAIL or t == INFO:
            rec = open_invokes.pop(p, None)
            if rec is not None:
                rec[1] = op
                if t == OK:
                    order.append(rec)

    # Event pass.  Six columns an event here; the four ghost columns are
    # filled on the finished array, for the ghost rows only.
    events: List[Tuple[int, ...]] = []
    ops: List[Op] = []
    free: List[int] = []
    next_slot = 0
    crashed: List[int] = []
    gclasses: dict = {}     # class key -> [ghost slots, in enter order]
    ghosts: List[Tuple[int, Any, int]] = []  # (event row, class key, rank)
    pure_fs: Set[int] = set(model.pure_read_fs) if model else set()
    encode = model.encode_op if model is not None else None
    f = a = b = 0

    for rec in order:
        s = rec[2]
        if s >= 0:  # second visit: the ok completion
            events.append((EV_RETURN, s, 0, 0, 0, rec[3]))
            free.append(s)
            continue
        op, comp = rec[0], rec[1]
        ctype = comp.type if comp is not None else INFO
        if ctype == FAIL:
            continue  # never took effect
        if ctype == OK:
            # History.complete parity: the invoke adopts its ok
            # completion's value; copied only where that changes it.
            v = comp.value
            if v is not None:
                own = op.value
                if v is not own and v != own:
                    op = op.with_(value=v)
        if encode is not None:
            f, a, b = encode(op)
            if ctype == INFO and f in pure_fs and a == UNKNOWN32:
                continue  # crashed read, unknown value: unconstraining
        elif ctype == INFO and op.f in pure_read_names and op.value is None:
            continue
        if free:
            s = free.pop()
        else:
            s = next_slot
            next_slot += 1
        op_id = len(ops)
        rec[2] = s
        rec[3] = op_id
        if ctype == INFO:
            # Class key: the op's semantics.  With a model, the int32
            # encoding; without (host tier), the raw (f, value) — the
            # all-zero placeholder encodings must not merge classes.
            key = (f, a, b) if encode is not None else (op.f,
                                                        repr(op.value))
            members = gclasses.setdefault(key, [])
            ghosts.append((len(events), key, len(members)))
            members.append(s)
            crashed.append(s)
        events.append((EV_ENTER, s, f, a, b, op_id))
        ops.append(op)

    if max_window is not None and next_slot > max_window:
        raise WindowOverflow(
            f"history needs {next_slot} pending-window slots "
            f"(> max {max_window}); raise max_window or shard the history")

    cols = np.zeros((len(events), 10), np.int32)
    cols[:, :6] = np.array(events, np.int32).reshape(-1, 6)
    cols[:, 7] = -1
    # Compact ghost positions: classes get contiguous ranges in discovery
    # order, each ghost at (class offset + rank).
    offsets: dict = {}
    off = 0
    for key, members in gclasses.items():
        offsets[key] = off
        off += len(members)
    for row, key, rank in ghosts:
        cols[row, 6:] = (1, gclasses[key][0], rank, offsets[key] + rank)
    return PreparedHistory(
        kind=cols[:, 0], slot=cols[:, 1], f=cols[:, 2],
        a=cols[:, 3], b=cols[:, 4], op_id=cols[:, 5], ghost=cols[:, 6],
        gcls=cols[:, 7], grank=cols[:, 8], gpos=cols[:, 9],
        window=next_slot, ops=ops, crashed_slots=tuple(crashed),
        n_ghosts=off,
    )
