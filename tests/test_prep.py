"""checker/prep.py: prepare's one pairing pass and one event pass against
the three-pass form it replaced (PR 36), kept here as the plain reference:
``client_ops().complete()``, a second ``pair_index()``, then the event loop
with its per-event tuple rebuild for ``gpos``."""

import random
from typing import List, Set, Tuple

import numpy as np
import pytest

from jepsen_tpu import independent
from jepsen_tpu.checker.prep import (
    EV_ENTER, EV_RETURN, PreparedHistory, WindowOverflow, prepare,
)
from jepsen_tpu.history import FAIL, History, INFO, INVOKE, NEMESIS, OK, Op
from jepsen_tpu.models import get_model
from jepsen_tpu.models.base import UNKNOWN32
from jepsen_tpu.synth import (
    cas_register_history, doomed_cas_padding, ghost_write_burst,
    multi_register_history,
)

COLUMNS = ("kind", "slot", "f", "a", "b", "op_id", "ghost", "gcls", "grank",
           "gpos")
OP_FIELDS = ("process", "type", "f", "value", "time", "index", "error",
             "extra")


def reference_prepare(history, model, max_window=None,
                      pure_read_names=("read", "r")):
    """The parent's ``_prepare`` (commit 6ddf170), as it was."""
    h = history.client_ops().complete()
    pairs = h.pair_index()

    events: List[Tuple[int, ...]] = []
    ops: List[Op] = []
    free: List[int] = []
    next_slot = 0
    slot_of: dict = {}
    opid_of: dict = {}
    crashed: List[int] = []
    gclasses: dict = {}
    pure_fs: Set[int] = set(model.pure_read_fs) if model else set()

    def alloc_slot() -> int:
        nonlocal next_slot
        if free:
            return free.pop()
        s = next_slot
        next_slot += 1
        return s

    for i, op in enumerate(h):
        if op.type == INVOKE:
            j = pairs[i]
            comp = h[j] if j >= 0 else None
            ctype = comp.type if comp is not None else INFO
            if ctype == FAIL:
                continue
            if model is not None:
                f, a, b = model.encode_op(op)
                if ctype == INFO and f in pure_fs and a == UNKNOWN32:
                    continue
            else:
                f = a = b = 0
                if (ctype == INFO and op.f in pure_read_names
                        and op.value is None):
                    continue
            s = alloc_slot()
            slot_of[i] = s
            opid_of[i] = len(ops)
            if ctype == INFO:
                key = (f, a, b) if model is not None else (op.f,
                                                          repr(op.value))
                members = gclasses.setdefault(key, [])
                cls, rank = (members[0] if members else s), len(members)
                members.append(s)
                events.append((EV_ENTER, s, f, a, b, len(ops), 1, cls, rank,
                               0))
                crashed.append(s)
            else:
                events.append((EV_ENTER, s, f, a, b, len(ops), 0, -1, 0, 0))
            ops.append(op)
        elif op.type == OK:
            j = pairs[i]
            if j in slot_of:
                s = slot_of[j]
                events.append((EV_RETURN, s, 0, 0, 0, opid_of[j], 0, -1, 0,
                               0))
                free.append(s)

    if max_window is not None and next_slot > max_window:
        raise WindowOverflow(
            f"history needs {next_slot} pending-window slots "
            f"(> max {max_window}); raise max_window or shard the history")

    offsets: dict = {}
    off = 0
    for key, members in gclasses.items():
        offsets[key] = off
        off += len(members)
    class_off = {members[0]: offsets[key]
                 for key, members in gclasses.items()}
    events = [e[:9] + (class_off[e[7]] + e[8],) if e[6] else e
              for e in events]

    cols = np.array(events, np.int32).reshape(-1, 10)
    return PreparedHistory(
        kind=cols[:, 0], slot=cols[:, 1], f=cols[:, 2],
        a=cols[:, 3], b=cols[:, 4], op_id=cols[:, 5], ghost=cols[:, 6],
        gcls=cols[:, 7], grank=cols[:, 8], gpos=cols[:, 9],
        window=next_slot, ops=ops, crashed_slots=tuple(crashed),
        n_ghosts=off,
    )


def assert_same(got: PreparedHistory, want: PreparedHistory) -> None:
    for c in COLUMNS:
        g, w = getattr(got, c), getattr(want, c)
        assert g.dtype == w.dtype == np.int32, c
        assert g.shape == w.shape, c
        assert np.array_equal(g, w), c
    assert got.window == want.window
    assert got.crashed_slots == want.crashed_slots
    assert got.n_ghosts == want.n_ghosts
    assert len(got.ops) == len(want.ops)
    for i, (g, w) in enumerate(zip(got.ops, want.ops)):
        for field in OP_FIELDS:
            assert getattr(g, field) == getattr(w, field), (i, field)


# -- the histories ----------------------------------------------------------

def _inv(p, f, v=None, **kw):
    return Op(process=p, type=INVOKE, f=f, value=v, **kw)


def _done(p, t, f, v=None, **kw):
    return Op(process=p, type=t, f=f, value=v, **kw)


def _nem(f, **kw):
    return Op(process=NEMESIS, type=INFO, f=f, **kw)


def _keyed(n_keys, n_ops, crash_p, nemesis_every=0):
    """One history of ``n_keys`` interleaved register lanes, the shape of
    the benchmark's keyed cells, and the split the checker makes of it."""
    rng = random.Random(n_keys * 1000 + n_ops)
    lanes = [list(cas_register_history(n_ops, concurrency=6, crash_p=crash_p,
                                       seed=100 + k)) for k in range(n_keys)]
    ops = []
    while any(lanes):
        k = rng.choice([k for k, lane in enumerate(lanes) if lane])
        op = lanes[k].pop(0)
        ops.append(op.with_(process=op.process + 10 * k,
                            value=independent.tuple_(k, op.value)))
        if nemesis_every and len(ops) % nemesis_every == 0:
            ops.append(_nem(rng.choice(["start-partition", "stop-partition"]),
                            time=op.time))
    return independent.subhistories(History(ops, reindex=True))


def keyed_lane():
    return _keyed(4, 200, 0.005)[2]


def nemesis_lane():
    h = _keyed(3, 120, 0.03, nemesis_every=7)[1]
    assert sum(op.process == NEMESIS for op in h) > 20
    return h


def crash_history():
    """Ghost classes of one, two and many members, interleaved."""
    base = list(cas_register_history(400, concurrency=8, crash_p=0.04,
                                     seed=7))
    doomed = doomed_cas_padding(6)
    burst = (ghost_write_burst(3, base_value=3) + ghost_write_burst(
        3, start_process=3000, base_value=3))  # each value crashes twice
    twins = [_inv(4000 + i, "cas", [7777, 1]) for i in range(4)]
    rng = random.Random(11)
    for extra in (doomed, burst, twins):
        for op in extra:
            base.insert(rng.randrange(len(base) // 2, len(base)), op)
    # the invoke of a pair must still precede its completion
    seen, ops = set(), []
    for op in base:
        if op.type != INVOKE and op.process >= 2000 \
                and op.process not in seen:
            continue
        seen.add(op.process)
        ops.append(op)
    return History(ops, reindex=True)


def multireg_history():
    return multi_register_history(300, keys=3, concurrency=6, crash_p=0.03,
                                  seed=5)


def fail_pairs():
    return History([
        _inv(0, "write", 1), _inv(1, "cas", [1, 2]), _done(0, OK, "write", 1),
        _done(1, FAIL, "cas", [1, 2]), _inv(1, "write", 3),
        _done(1, FAIL, "write", 3), _inv(0, "read"), _inv(1, "cas", [1, 4]),
        _done(1, OK, "cas", [1, 4]), _done(0, OK, "read", 4)])


def info_read_unknown():
    return History([
        _inv(0, "write", 2), _inv(1, "read"), _inv(2, "read"),
        _done(1, INFO, "read"), _done(0, OK, "write", 2),
        _inv(3, "write", 4), _done(3, INFO, "write", 4),
        _done(2, INFO, "read", 2),      # an info read that saw a value stays
        _inv(4, "read"), _done(4, OK, "read", 2)])


def trailing_invoke():
    return History([
        _inv(0, "write", 1), _done(0, OK, "write", 1), _inv(1, "read"),
        _inv(0, "write", 2), _done(1, OK, "read", 1), _inv(2, "read")])


def invokes_twice():
    return History([
        _inv(0, "write", 1), _inv(1, "read"), _inv(0, "write", 2),
        _done(0, OK, "write", 2), _done(1, OK, "read", 2),
        _inv(1, "read"), _inv(1, "read"), _inv(1, "cas", [2, 3]),
        _done(1, FAIL, "cas", [2, 3])])


def orphan_completion():
    return History([
        _done(0, OK, "read", 1), _inv(1, "write", 1),
        _done(2, INFO, "write", 9), _done(1, OK, "write", 1),
        _done(1, OK, "write", 1), _done(0, FAIL, "cas", [1, 2]),
        _inv(0, "read"), _done(0, OK, "read", 1)])


def ops_without_index():
    """``History.adopt`` takes the list as it is: ``index`` stays ``None``
    and prepare numbers such an op by its place among the client ops."""
    return History.adopt([
        _nem("start"), _inv(0, "write", 1), _nem("stop"), _inv(1, "read"),
        _done(0, OK, "write", 1), _nem("start"), _done(1, OK, "read", 1),
        _inv(0, "read", index=40), _done(0, OK, "read", 1, index=41),
        _inv(2, "write", 5), _nem("stop"), _done(2, INFO, "write", 5)])


def ok_value_none():
    return History([
        _inv(0, "write", 3), _done(0, OK, "write"), _inv(1, "read"),
        _done(1, OK, "read"), _inv(0, "cas", [3, 4]), _done(0, OK, "cas"),
        _inv(1, "read"), _done(1, OK, "read", 4)])


def empty_history():
    return History([])


def fuzz_history():
    """Every type in any order on a few processes: pairs break every way
    they can, and completions carry values their invokes did not."""
    rng = random.Random(36)
    ops = []
    for i in range(1500):
        if rng.random() < 0.05:
            ops.append(_nem(rng.choice(["start", "stop"]), time=i))
            continue
        f = rng.choice(["read", "write"])   # a pair may mix the two
        v = rng.choice([None, rng.randrange(5), rng.randrange(5)])
        ops.append(Op(process=rng.randrange(7),
                      type=rng.choice([INVOKE, INVOKE, OK, OK, FAIL, INFO]),
                      f=f, value=v, time=i,
                      error=rng.choice([None, "timeout"]),
                      extra={"node": "n1"} if i % 9 == 0 else {}))
    return History(ops)


CASES = {
    "keyed-200-lane": (keyed_lane, "cas-register"),
    "nemesis-lane": (nemesis_lane, "cas-register"),
    "crash-ghost-classes": (crash_history, "cas-register"),
    "multi-register": (multireg_history, "multi-register"),
    "fail-pairs": (fail_pairs, "cas-register"),
    "info-read-unknown": (info_read_unknown, "cas-register"),
    "trailing-invoke": (trailing_invoke, "cas-register"),
    "invokes-twice": (invokes_twice, "cas-register"),
    "orphan-completion": (orphan_completion, "cas-register"),
    "ops-without-index": (ops_without_index, "cas-register"),
    "ok-value-none": (ok_value_none, "cas-register"),
    "empty": (empty_history, "cas-register"),
    "fuzz": (fuzz_history, "cas-register"),
}


def _model(name, with_model):
    return get_model(name) if with_model else None


@pytest.mark.parametrize("with_model", [True, False], ids=["jax", "none"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_prepare_equals_three_pass_reference(case, with_model):
    build, model_name = CASES[case]
    model = _model(model_name, with_model)
    want = reference_prepare(build(), model)
    got = prepare(build(), model)
    assert_same(got, want)
    if case == "crash-ghost-classes":
        sizes = np.bincount(want.gcls[want.ghost == 1])
        assert want.n_ghosts > 20 and (sizes > 2).any() and (sizes == 1).any()
        assert sorted(want.gpos[want.ghost == 1]) == list(
            range(want.n_ghosts))
    if case == "ops-without-index":
        assert [op.index for op in got.ops] == [0, 1, 40, 6]


@pytest.mark.parametrize("with_model", [True, False], ids=["jax", "none"])
@pytest.mark.parametrize("case", ["crash-ghost-classes", "keyed-200-lane"])
def test_max_window_overflow_is_the_references(case, with_model):
    build, model_name = CASES[case]
    model = _model(model_name, with_model)
    window = reference_prepare(build(), model).window
    assert_same(prepare(build(), model, max_window=window),
                reference_prepare(build(), model, max_window=window))
    with pytest.raises(WindowOverflow) as want:
        reference_prepare(build(), model, max_window=window - 1)
    with pytest.raises(WindowOverflow) as got:
        prepare(build(), model, max_window=window - 1)
    assert str(got.value) == str(want.value)


def test_pure_read_names_are_the_callers():
    h = History([_inv(0, "get"), _done(0, INFO, "get"), _inv(1, "read"),
                 _done(1, INFO, "read")])
    for names in (("read", "r"), ("get",), ()):
        assert_same(prepare(h, None, pure_read_names=names),
                    reference_prepare(h, None, pure_read_names=names))
    assert prepare(h, None, pure_read_names=("get",)).n_ops == 1


def reference_invokes(h, want):
    """The caller's own invoke behind each of ``want.ops``, by index."""
    by_index = {op.index: op for op in h.ops
                if op.type == INVOKE and op.process != NEMESIS}
    return [by_index[w.index] for w in want.ops]


@pytest.mark.parametrize("with_model", [True, False], ids=["jax", "none"])
@pytest.mark.parametrize("case", ["nemesis-lane", "multi-register",
                                  "ok-value-none", "fuzz"])
def test_prepare_reads_the_history_once(case, with_model, monkeypatch):
    """No intermediate ``History``, a ``with_`` copy only for an invoke
    whose ok completion brings another value, and nothing written onto the
    history the caller owns."""
    build, model_name = CASES[case]
    model = _model(model_name, with_model)
    h = build()
    want = reference_prepare(h, model)
    held = list(h.ops)
    before = [dict(op.__dict__) for op in held]
    # what the completed view changes: by the reference, which copies always
    changed = sum(w.value != o.value
                  for w, o in zip(want.ops, reference_invokes(h, want)))

    def refuse(*a, **kw):
        raise AssertionError("prepare built a History")
    copies = []
    with_ = Op.with_

    def counting_with_(self, **kw):
        copies.append(kw)
        return with_(self, **kw)
    monkeypatch.setattr(History, "__init__", refuse)
    monkeypatch.setattr(History, "client_ops", refuse)
    monkeypatch.setattr(History, "complete", refuse)
    monkeypatch.setattr(History, "pair_index", refuse)
    monkeypatch.setattr(Op, "with_", counting_with_)
    got = prepare(h, model)
    monkeypatch.undo()

    assert_same(got, want)
    assert len(copies) == changed
    assert all(set(kw) == {"value"} for kw in copies)
    assert h._pairs is None
    assert len(h.ops) == len(held)
    assert all(a is b for a, b in zip(h.ops, held))
    assert [dict(op.__dict__) for op in held] == before
    # an op whose completed view is itself is handed on, not copied
    ids = {id(op) for op in held}
    assert sum(id(op) not in ids for op in got.ops) == changed
    if case != "ok-value-none":
        assert 0 < changed < len(got.ops)


def test_prepare_keeps_nothing_between_calls():
    """The same ``History`` again gives fresh arrays and a fresh ``ops``
    list; an edit to the history shows in the next call."""
    h = fail_pairs()
    first, second = prepare(h), prepare(h)
    assert_same(first, second)
    assert first.ops is not second.ops and first.kind is not second.kind
    h.ops[3] = h.ops[3].with_(type=OK)
    assert_same(prepare(h), reference_prepare(h, None))
    assert prepare(h).n_ops == first.n_ops + 1
