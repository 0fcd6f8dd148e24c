"""A key the batch refutes is searched once: ``IndependentChecker`` takes
the refuting op from ``check_batch``'s own answer to the host witness and
the render (``Linearizable.explain_refutation``), and no single-history
device search runs again under ``entry.rederive``.  Nothing softens the
batch's refutation: not a witness that disagrees, runs out of budget or
crashes, and not ``explain=False``.
"""

import importlib
import os

import pytest

from jepsen_tpu import independent
from jepsen_tpu.checker.linearizable import linearizable
from jepsen_tpu.history import History
from jepsen_tpu.models import get_model
from jepsen_tpu.synth import cas_register_history, corrupt_reads

KEYS = 8

#: the module (``jepsen_tpu.checker`` exports the function under its name)
lin_mod = importlib.import_module("jepsen_tpu.checker.linearizable")


@pytest.fixture(scope="module")
def model():
    return get_model("cas-register")


def keyed_history(seed: int, crash_p: float = 0.005, keys: int = KEYS,
                  n_ops: int = 40) -> History:
    """``keys`` register histories under one keyed history, every 4th key
    with one corrupted read."""
    lanes = [cas_register_history(n_ops, concurrency=4, crash_p=crash_p,
                                  seed=1000 * seed + k) for k in range(keys)]
    for k in range(0, keys, 4):
        lanes[k] = corrupt_reads(lanes[k], n=1, seed=seed)
    return History(
        [op.with_(process=op.process + 10 * k,
                  value=independent.tuple_(k, op.value))
         for k, h in enumerate(lanes) for op in h], reindex=True)


@pytest.mark.parametrize("crash_p", [0.0, 0.02])
@pytest.mark.parametrize("seed", [1, 2, 3, 2**31 + 7])
def test_refuted_leaves_equal_the_single_history_checker(model, seed,
                                                         crash_p):
    keyed = keyed_history(seed, crash_p)
    subs = independent.subhistories(keyed)
    res = independent.checker(linearizable(model)).check({}, keyed)
    alone = {k: linearizable(model).check({}, h) for k, h in subs.items()}
    assert res["failures"] == sorted(k for k, r in alone.items()
                                     if r["valid"] is not True)
    assert set(range(0, KEYS, 4)) <= set(res["failures"])
    assert res["valid"] is False and "disagreements" not in res
    for k, want in alone.items():
        got = res["results"][k]
        assert got["analyzer"] == "wgl-tpu-batch"
        assert got["valid"] is want["valid"], k
        if want["valid"]:
            assert set(got) == {"valid", "analyzer", "configs-explored"}
            continue
        assert got["op"] == want["op"] and got["op"]["index"] >= 0, k
        assert got["witness"]["valid"] is False \
            and want["witness"]["valid"] is False
        assert got["witness"]["op"] == want["witness"]["op"]
        assert "recheck" not in got
        # the batch's leaf, not a single-history engine's
        assert not {"window", "capacity", "max-capacity-reached"} & set(got)


def test_a_witness_that_finds_the_prefix_linearizable_softens_nothing(
        model, monkeypatch):
    keyed = keyed_history(5)
    monkeypatch.setattr(lin_mod, "cpu_witness",
                        lambda *a, **kw: {"valid": True,
                                          "analyzer": "wgl-cpu"})
    res = independent.checker(linearizable(model)).check({}, keyed)
    assert res["valid"] is False
    assert res["failures"] == res["disagreements"] == [0, 4]
    for k in (0, 4):
        leaf = res["results"][k]
        assert leaf["valid"] is False and leaf["op"]["index"] >= 0
        assert leaf["analyzer"] == "wgl-tpu-batch"
        assert leaf["recheck"]["valid"] is True
        assert leaf["witness"] == {"valid": True, "analyzer": "wgl-cpu"}


def test_a_witness_out_of_budget_degrades_the_witness_alone(model, rec):
    keyed = keyed_history(5)
    res = independent.checker(
        linearizable(model, witness_budget=1)).check({}, keyed)
    assert res["valid"] is False and res["failures"] == [0, 4]
    assert "disagreements" not in res
    for k in (0, 4):
        leaf = res["results"][k]
        assert leaf["valid"] is False and leaf["op"]["index"] >= 0
        assert leaf["witness"] == {"error": "witness search exceeded budget"}
        assert "recheck" not in leaf
    closes = {e["args"]["key"]: e["args"]["confirmed"]
              for e in rec.snapshot() if e["name"] == "entry.rederive"}
    assert closes == {0: False, 4: False}


def test_a_witness_that_crashes_degrades_the_witness_alone(model,
                                                           monkeypatch):
    def boom(*a, **kw):
        raise RuntimeError("oracle fell over")
    keyed = keyed_history(5)
    monkeypatch.setattr(lin_mod, "cpu_witness", boom)
    res = independent.checker(linearizable(model)).check({}, keyed)
    assert res["valid"] is False and res["failures"] == [0, 4]
    assert "disagreements" not in res
    for k in (0, 4):
        leaf = res["results"][k]
        assert leaf["valid"] is False and leaf["op"]["index"] >= 0
        assert "oracle fell over" in leaf["witness"]["error"]
        assert "recheck" not in leaf


def test_each_refuted_key_renders_in_its_own_result_dir(model, tmp_path):
    keyed = keyed_history(5)
    res = independent.checker(linearizable(model)).check(
        {}, keyed, {"store_dir": str(tmp_path)})
    assert res["failures"] == [0, 4]
    for k in range(KEYS):
        svg = tmp_path / "independent" / str(k) / "linear.svg"
        assert svg.exists() is (k in (0, 4)), k
        if k in (0, 4):
            assert res["results"][k]["render"] == str(svg)
            assert os.path.getsize(svg) > 0
        else:
            assert "render" not in res["results"][k]


def test_explain_off_asks_the_host_nothing(model, rec):
    keyed = keyed_history(5)
    res = independent.checker(
        linearizable(model, explain=False)).check({}, keyed)
    assert res["valid"] is False and res["failures"] == [0, 4]
    assert "disagreements" not in res
    for k in (0, 4):
        leaf = res["results"][k]
        assert leaf["valid"] is False and leaf["op"]["index"] >= 0
        assert "witness" not in leaf and "recheck" not in leaf
    names = [e["name"] for e in rec.snapshot()]
    assert "witness.cpu" not in names and "drivers.check" not in names
    assert names.count("entry.rederive") == 2


def test_the_single_history_tail_is_the_same_method(model, tmp_path):
    """``Linearizable.check`` ends in the same tail: the single engine
    attached its witness already, so the tail adds the render alone; a
    host solver's refutation is its own witness."""
    h = corrupt_reads(cas_register_history(40, concurrency=4, seed=9), n=1)
    res = linearizable(model).check({}, h, {"store_dir": str(tmp_path)})
    assert res["valid"] is False and res["analyzer"] == "wgl-tpu"
    assert res["witness"]["valid"] is False
    assert res["render"] == str(tmp_path / "linear.svg")
    host = linearizable(model, algorithm="cpu").check({}, h)
    assert host["valid"] is False and "witness" not in host
    assert host["op"] == res["op"]
