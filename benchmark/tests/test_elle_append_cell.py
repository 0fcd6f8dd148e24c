"""The ``elle-append-10k`` configuration and its cell
``elle-append10k.offline`` (PR 37): a toy run of the ``offline_elle`` loop
end to end, sound and traced; its two controls at toy size and at the cell's
own; the operation count of the roofline reader; the manifest's entries.
What the program does with such histories at small sizes is
``tests/test_elle_append_cell.py``'s.  CPU, no chip:

  JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_elle_append_cell.py -q
"""

from __future__ import annotations

import json
import time

import pytest

from controls_elle import CONTROLS, PROGRAM_CONTROLS
from gen.histories import GENERATORS
from harness import report
from harness.loops import offline_elle, offline_requires
from harness.manifest import Cell, manifest
from readers import closure_roofline

MAN = manifest()
NEW_CELL = "elle-append10k.offline"
NEW_LAYERS = ["elle.host_pass_s", "elle.readback_wait_share",
              "kernels.closure_mxu_share"]
COUNTS = {"verdict_mismatches", "anomaly_mismatches", "flag_mismatches",
          "unknown_verdicts", "host_answers", "txn_count_drift"}


def toy_cell():
    cell = Cell(NEW_CELL, MAN)
    cell.config.update(txns=200, key_count=3, max_writes_per_key=16)
    return cell


def fake_chip(chips):
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def toy_run(capsys, traced=False, seed=2**31 + 11):
    rc = offline_elle.run(toy_cell(), seed, 0.2, traced, time.monotonic(),
                          report.Log(), require_chip=fake_chip)
    out, err = capsys.readouterr()
    assert rc == 0
    assert err.splitlines()[-1].startswith("compared ")
    return json.loads(out.splitlines()[-1]), out


def test_toy_run_is_correct(capsys):
    line, out = toy_run(capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"verdict_s", "setup_s"}
    assert line["attempted"] >= 1 and list(line)[-1] == "compared"
    assert set(line["compared"]) == COUNTS
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in line["compared"].values())
    assert "reference: valid True" in out and "analyzer elle-tpu" in out
    # the window's answers and the two probes'
    assert line["attempted"] >= 3 and out.count("] probe ") == 2
    for name in ("late_reader", "stale_read"):
        said = next(l for l in out.splitlines() if f"probe {name}:" in l)
        assert "'cyclic': True" in said.split("reference:")[0]
        assert "valid True" not in said


def test_toy_traced_run_reports_the_layers(capsys):
    line, _ = toy_run(capsys, traced=True)
    assert line["correct"] is True
    assert {"elle.host_pass_s", "elle.readback_wait_share",
            "entry.host_answers", "compile.window_compiles",
            "compile.trace_s", "setup.warmup_excess_s"} <= set(
        line["metrics"])
    assert "verdict_s" not in line["metrics"]
    assert line["metrics"]["entry.host_answers"]["value"] == 0
    assert 0 < line["metrics"]["elle.readback_wait_share"]["value"] < 100
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    # on the CPU no device plane is read: nothing, never a 0 share
    for name in ("device.idle_share", "kernels.closure_mxu_share",
                 "drivers.launches_per_call"):
        assert name not in line["metrics"], name


def test_a_program_without_the_counter_exits_before_anything(monkeypatch):
    cell = toy_cell()
    cell.traffic["requires"] = ["jepsen_tpu.elle_tpu.engine:no_such_stats"]
    called = []
    with pytest.raises(offline_requires.Lacking) as e:
        offline_elle.run(cell, 1, 0.2, False, time.monotonic(), report.Log(),
                         require_chip=lambda chips: called.append(chips))
    assert e.value.code and "no_such_stats" in str(e.value.code)
    assert called == []


def control_verdict(controls, control, size):
    cell = toy_cell() if size == "toy" else Cell(NEW_CELL, MAN)
    gen = GENERATORS[cell.traffic["generator"]](
        cell.config, cell.traffic["params"], 2**31 + 37)
    verdict = controls[control](cell, gen["records"], 37)
    # one answer of the window and the two probes'
    assert verdict["attempted"] == 3 and verdict["correct"] is False
    return verdict, {k for k, c in verdict["compared"].items()
                     if not c["ok"]}


@pytest.mark.parametrize("size", ["toy", "cell"])
@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_control_is_not_correct(control, size):
    verdict, out = control_verdict(CONTROLS, control, size)
    # the window's answer stands; the late_reader probe tells in each, the
    # stale one where its cycle needs a realtime edge or was overlooked
    assert verdict["failed"] in ((2,) if control == "overlooked" else (1, 2))
    # each by a wrong answer's three counts and by nothing else
    assert out == {"verdict_mismatches", "anomaly_mismatches",
                   "flag_mismatches"}, out


@pytest.mark.parametrize("control", sorted(PROGRAM_CONTROLS))
def test_program_control_is_not_correct(control):
    verdict, out = control_verdict(PROGRAM_CONTROLS, control, "toy")
    assert verdict["failed"] in (1, 2)
    assert out == {"verdict_mismatches", "anomaly_mismatches",
                   "flag_mismatches"}, out


def test_a_probe_cut_short_stands_for_false_and_never_for_true():
    want = {"valid": False, "anomaly_types": ["G-single-realtime"],
            "unnamed_cycle": False, "count": 9,
            "flags": {"cyclic": True, "g0": False, "g1c": False,
                      "g-single": True}}
    cut = {"valid": "unknown", "cycle-search-truncated": True,
           "anomaly-types": [], "device-flags": dict(want["flags"]),
           "count": 9, "analyzer": "elle-tpu"}

    def counts(res, budgeted=True):
        return {k for k, v in offline_elle.judge(
            res, want, None, ["elle-tpu"], lambda t: set(t),
            budgeted).items() if v}

    assert counts(cut) == set()
    # the same answer inside the window is a host answer and no verdict
    assert counts(cut, budgeted=False) == {
        "unknown_verdicts", "anomaly_mismatches", "host_answers"}
    assert counts(dict(cut, valid=True)) == {"verdict_mismatches"}
    assert counts({**cut, "device-flags": dict.fromkeys(
        want["flags"], False)}) == {"flag_mismatches"}
    assert counts({k: v for k, v in cut.items()
                   if k != "cycle-search-truncated"}) == {
        "unknown_verdicts", "anomaly_mismatches"}
    assert counts(dict(cut, analyzer="elle-cpu")) == {"host_answers"}
    assert counts(dict(cut, count=8)) == {"txn_count_drift"}
    # a valid history under a cut search proves nothing: not the wanted False
    assert {k for k, v in offline_elle.judge(
        cut, dict(want, valid=True), None, ["elle-tpu"], set, True).items()
        if v} == {"verdict_mismatches"}


def test_flops_by_hand():
    call = {"n_pad": 9504, "e_pad": 19136, "closure_rounds": 42,
            "layer_builds": 3}
    assert closure_roofline.flops(call) == \
        42 * 2 * 9504 ** 3 + 3 * 2 * 19136 * 9504 ** 2


def test_the_manifest_gained_the_cell_at_the_end_of_its_lists():
    assert MAN["configs"][-1]["name"] == "elle-append-10k"
    assert MAN["workloads"][-1]["name"] == NEW_CELL
    assert MAN["workloads"][-1]["chips"] == 1
    assert len(MAN["workloads"][-1]["why"]) <= 200
    assert [m["name"] for m in MAN["per_layer"]][-3:] == NEW_LAYERS
    gained = []
    for group in ("end_to_end", "per_layer"):
        for m in MAN[group]:
            listed = m.get("workloads")
            if m["name"] in NEW_LAYERS:
                assert listed == [NEW_CELL]
            elif listed and NEW_CELL in listed:
                assert listed[-1] == NEW_CELL and listed.count(NEW_CELL) == 1
                gained.append(m["name"])
    assert gained == [
        "verdict_s", "entry.host_answers",
        "drivers.launches_per_call", "compile.window_compiles",
        "compile.setup_cache_misses", "device.idle_share",
        "device.peak_hbm_bytes", "setup.warmup_excess_s",
        "compile.trace_s", "compile.lower_s", "compile.load_s",
        "compile.eager_s", "setup.warmup_unnamed_s"]
    for m in MAN["per_layer"][-3:]:
        assert m["moves"] == "verdict_s"
