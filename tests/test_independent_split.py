"""The one-pass per-key split (independent.subhistories) against the
per-key API it replaced in the checker: history_keys + subhistory."""

import random

import pytest

from jepsen_tpu import independent
from jepsen_tpu.history import History, INFO, INVOKE, NEMESIS, OK, Op


def _pair(p, f, k, v, **kw):
    return [Op(process=p, type=INVOKE, f=f, value=(k, v), **kw),
            Op(process=p, type=OK, f=f, value=(k, v), **kw)]


def _nem(f, value=None, **kw):
    return Op(process=NEMESIS, type=INFO, f=f, value=value, **kw)


def _random_history(seed, n=3000, n_keys=37):
    rng = random.Random(seed)
    ops = []
    for i in range(n):
        r = rng.random()
        if r < 0.04:
            ops.append(_nem(rng.choice(["start", "stop"]), time=i))
        elif r < 0.05:
            ops.append(_nem("kill", value=(rng.randrange(n_keys), "n1")))
        elif r < 0.08:  # unkeyed client op: dropped
            ops.append(Op(process=rng.randrange(8), type=INVOKE, f="read",
                          value=rng.choice([None, 3, [1, 2], (1, 2, 3)])))
        else:
            ops.append(Op(process=rng.randrange(8),
                          type=rng.choice([INVOKE, OK, "fail", INFO]),
                          f=rng.choice(["read", "write", "cas"]),
                          value=(rng.randrange(n_keys), rng.randrange(5)),
                          time=i, error=rng.choice([None, "timeout"]),
                          extra={"node": f"n{i % 5}"} if i % 7 == 0 else {}))
    return History(ops)


CASES = {
    "interleaved-keys": lambda: History(
        _pair(0, "write", "a", 1) + _pair(1, "write", "b", 2)
        + _pair(0, "read", "b", 2) + _pair(1, "read", "a", 1)),
    "nemesis-before-any-key": lambda: History(
        [_nem("start"), _nem("stop")] + _pair(0, "write", 1, 5)
        + _pair(1, "write", 2, 6)),
    "nemesis-between-and-before-later-key": lambda: History(
        _pair(0, "write", 1, 5)[:1] + [_nem("start", value="majority")]
        + _pair(0, "write", 1, 5)[1:] + [_nem("stop")]
        + _pair(1, "read", 2, None) + [_nem("start")]
        + _pair(0, "read", 1, 5)),
    "nemesis-2-tuple-value": lambda: History(
        _pair(0, "write", "n1", 5) + [_nem("kill", value=("n1", "n2"))]
        + [_nem("kill", value=("n9", "n2"))] + _pair(0, "read", "n1", 5)),
    "unkeyed-client-ops": lambda: History(
        [Op(process=0, type=INVOKE, f="read", value=None)]
        + _pair(0, "write", 1, 5)
        + [Op(process=1, type=OK, f="read", value=[1, 2]),
           Op(process=1, type=OK, f="read", value=(None, 2)),
           Op(process=1, type=OK, f="txn", value=(1, 2, 3))]),
    "keys-1-and-1.0": lambda: History(
        _pair(0, "write", 1, 5) + _pair(1, "write", 1.0, 6)
        + _pair(0, "write", True, 7) + _pair(1, "read", 2, None)),
    "empty": lambda: History([]),
    "nemesis-only": lambda: History([_nem("start"), _nem("stop")]),
    "one-key": lambda: History(
        _pair(0, "write", 7, 1) + [_nem("start")] + _pair(1, "read", 7, 1)),
    "carried-fields": lambda: History(
        _pair(0, "write", 1, 5, time=10, extra={"node": "n1"})
        + [_nem("start", time=11, extra={"targets": ["n1"]}),
           Op(process=0, type="fail", f="cas", value=(1, (5, 6)), time=12,
              error="timeout", index=40)]),
    "random-3000": lambda: _random_history(26),
}


def _per_key(h):
    return {k: independent.subhistory(k, h)
            for k in independent.history_keys(h)}


@pytest.mark.parametrize("name", list(CASES))
def test_one_pass_split_equals_per_key_scans(name):
    h = CASES[name]()
    want, got = _per_key(h), independent.subhistories(h)
    assert list(got) == list(want)  # first-appearance order
    for k in want:
        assert isinstance(got[k], History)
        # Op is a dataclass: == covers process, type, f, value, time,
        # index, error and extra
        assert got[k].ops == want[k].ops, k
        assert [o.index for o in got[k]] == list(range(len(got[k])))
    # the input is left as it was
    assert h == CASES[name]()


def test_split_calls_key_of_once_per_entry(monkeypatch):
    """A count, not a time: the per-key scans cost (keys + 1) x entries
    calls of key_of; one pass costs entries."""
    ops = []
    for i in range(50):
        for k in range(64):
            ops.append(Op(process=k, type=INVOKE if i % 2 == 0 else OK,
                          f="write", value=(k, i)))
    h = History(ops)
    calls = []
    inner = independent.key_of

    def counting(op):
        calls.append(1)
        return inner(op)

    monkeypatch.setattr(independent, "key_of", counting)
    subs = independent.subhistories(h)
    split_calls = len(calls)
    assert len(subs) == 64 and all(len(s) == 50 for s in subs.values())
    assert split_calls <= 2 * len(h)
    del calls[:]
    _per_key(h)
    assert len(calls) == 65 * len(h)


def test_checker_splits_through_the_one_pass(monkeypatch):
    """IndependentChecker.check never falls back to per-key scans."""
    from jepsen_tpu.checker import Stats

    def boom(*a, **kw):
        raise AssertionError("per-key scan called from the checker")

    h = CASES["nemesis-between-and-before-later-key"]()
    want = _per_key(h)
    monkeypatch.setattr(independent, "subhistory", boom)
    monkeypatch.setattr(independent, "history_keys", boom)
    seen = []

    class Spy(Stats):
        def check(self, test, history, opts=None):
            seen.append(history)
            return super().check(test, history, opts)

    res = independent.IndependentChecker(Spy(), max_workers=1).check(
        {"name": "t"}, h, {})
    assert list(res["results"]) == list(want) and res["key-count"] == 2
    assert seen == list(want.values())
