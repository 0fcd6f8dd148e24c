"""The ``multireg-10k`` configuration and its cell ``multireg10k.offline``
(PR 31): the files the manifest names (what ``test_benchmark.py`` checks of
every cell it finds, plus what is particular to this one), the controls at
the cell's own files, and a fission answer counted as a host's.  CPU, no
chip:

  JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

from __future__ import annotations

import json
import os

import pytest

from controls import CONTROLS, as_program_result
from gen import histories as H
from gen import multi_register as M
from harness import correct
from harness.loops.offline import reference_verdicts
from harness.manifest import Cell, manifest, plugin

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAN = manifest()
NEW_CELL = "multireg10k.offline"
CONTROLS_CELLS = ["cas10k-crash.offline", "cas10k-clean.offline"]
NEW_LAYERS = ["drivers.capacity_fill", "drivers.events_at_16k_share"]


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


# -- the manifest and the files it names ------------------------------------

def test_new_cell_reports_what_the_cas10k_cells_report():
    cell = Cell(NEW_CELL, MAN)
    assert cell.chips == 1 and len(cell.entry["why"]) <= 200
    assert {m["name"] for m in cell.end_to_end()} == {"verdict_s", "setup_s"}
    mine = {m["name"] for m in cell.per_layer()}
    for name in CONTROLS_CELLS:
        assert mine == {m["name"] for m in Cell(name, MAN).per_layer()}
    assert set(NEW_LAYERS) <= mine
    assert cell.traffic["verdict_metric"] == "verdict_s"
    assert cell.traffic["trace_seconds"] == 1 and "spans" not in cell.traffic
    assert callable(plugin("harness.loops", cell.traffic["loop"], "run"))
    assert H.GENERATORS[cell.traffic["generator"]] is M.multi_register
    assert callable(plugin("reference", cell.config["reference"], "check"))


def test_manifest_gained_entries_after_those_it_had():
    """Prefixes, not the whole lists: a later PR appends after these."""
    assert [c["name"] for c in MAN["configs"]][:4] == [
        "cas-register-10k", "keyed-register-200", "keyed-register-nemesis",
        "multireg-10k"]
    assert [w["name"] for w in MAN["workloads"]][:6] == [
        "cas10k-crash.offline", "keyed200.offline", "cas10k-clean.offline",
        "keyed-nemesis.offline", "keyed200-refuted.offline", NEW_CELL]
    names = [m["name"] for m in MAN["per_layer"]]
    at = names.index(NEW_LAYERS[0])
    assert names[at:at + 2] == NEW_LAYERS
    assert names.index("drivers.lane_fill.keyed") == at - 1
    for m, better in zip(MAN["per_layer"][at:at + 2], ("higher", "lower")):
        spec = load("layers", m["name"])
        assert {k: spec[k] for k in ("name", "layer", "unit", "moves")} == \
            {k: m[k] for k in ("name", "layer", "unit", "moves")}
        assert (m["layer"], m["moves"], m["unit"], m["better"]) == (
            "drivers", "verdict_s", "%", better)
        assert m["workloads"][:3] == [NEW_CELL] + CONTROLS_CELLS
        assert spec["reader"] == "program_stats"
        assert spec["args"]["stats"] == \
            "jepsen_tpu.checker.wgl_tpu:check_stats"
    # every list that held the two cas10k cells gained the cell at its end
    for group in ("end_to_end", "per_layer"):
        for m in MAN[group]:
            listed = m.get("workloads", [])
            if set(CONTROLS_CELLS) <= set(listed) and \
                    m["name"] not in NEW_LAYERS:
                assert listed[:3] == CONTROLS_CELLS + [NEW_CELL], m["name"]
            elif m["name"] not in NEW_LAYERS:
                assert NEW_CELL not in listed, m["name"]


def test_the_readers_read_the_programs_counter(monkeypatch):
    """``program_stats`` on the program's ``check_stats()``: nothing before
    a call (a denominator of 0), a share once there are events, and 0, not
    nothing, where no event was consumed at 16,384."""
    from jepsen_tpu.checker import wgl_tpu
    read = plugin("readers", "program_stats", "read")
    fill, at16k = (load("layers", n)["args"] for n in NEW_LAYERS)
    zero = dict.fromkeys(wgl_tpu.check_stats(), 0)
    monkeypatch.setattr(wgl_tpu, "check_stats", lambda: zero)
    assert read({}, **fill) is None and read({}, **at16k) is None
    monkeypatch.setattr(wgl_tpu, "check_stats", lambda: dict(
        zero, events_consumed=1000, cap_events=4096 * 1000,
        peak_events=1024 * 1000))
    assert read({}, **fill) == 25.0
    assert read({}, **at16k) == 0.0
    # a program from before the counter reads nothing
    monkeypatch.delattr(wgl_tpu, "check_stats")
    assert read({}, **fill) is None


def test_the_configuration_states_the_sources_shapes():
    entry = next(c for c in MAN["configs"] if c["name"] == "multireg-10k")
    config, old = load("configs", "multireg-10k"), \
        load("configs", "cas-register-10k")
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert len({c["source"] for c in MAN["configs"]}) == len(MAN["configs"])
    assert entry["reduced"] == config["reduced"] == []
    assert config["architecture"] is None
    assert (config["register_keys"], config["values"], config["ops"],
            config["read_p"], config["concurrency"]) == (3, 5, 10000, 0.5, 8)
    assert (config["ops"], config["concurrency"], config["values"]) == (
        old["ops"], old["concurrency"], old["values"])
    assert (config["model"], config["reference"]) == ("multi-register",
                                                      "wgl_multi_register")
    assert config["device_analyzers"] == ["wgl-tpu"]
    assert set(config["guarantees"]) == set(old["guarantees"])
    for k in ("refutation", "degraded", "path"):
        assert config["guarantees"][k] == old["guarantees"][k]
    assert "multi-register" in config["guarantees"]["consistency"]
    assert {"concurrency", "crash rate"} <= set(config["assumed"])
    # the model's own defaults are this deployment's: 3 keys, values under 16
    from jepsen_tpu.models import get_model
    model = get_model(config["model"])
    assert model.state_size == config["register_keys"]
    assert model.variant == (3, 4) and config["values"] <= 2 ** 4


def test_the_traffic_file():
    traffic = load("traffic", "offline-multireg")
    assert (traffic["loop"], traffic["generator_module"],
            traffic["generator"], traffic["entry"]) == (
        "offline_plug", "multi_register", "multi_register", "linearizable")
    # doomed_cas 0: test_benchmark.py's toy_cell reads the key of every
    # single-history cell; the generator has no such padding
    assert traffic["params"] == {"history_seed": 77, "crash_p": 0.0005,
                                 "doomed_cas": 0}


# -- the cell's own history -------------------------------------------------

@pytest.fixture(scope="module")
def full_size():
    cell = Cell(NEW_CELL, MAN)
    gen = H.GENERATORS[cell.traffic["generator"]](
        cell.config, cell.traffic["params"], 2**31 + 11)
    return cell, gen, reference_verdicts(cell, gen)


def test_full_size_history_is_what_perf_md_says(full_size):
    _, gen, want = full_size
    recs = gen["records"]
    assert gen["keyed"] is False and len(recs) == 20_000
    infos = [o for o in recs if o.type == H.INFO]
    assert len(infos) == 12
    assert sum(o.f == "write" for o in infos) == 7
    assert sum(o.type == H.INVOKE for o in recs) == 10_000
    assert sorted({o.process for o in recs}) == list(range(8))
    pending = peak = 0
    for o in recs:
        pending += 1 if o.type == H.INVOKE else -1 if o.type != H.INFO \
            or o.f == "read" else 0
        peak = max(peak, pending)
    assert peak == 15                   # the engine's window, before rounding
    assert {k for o in recs for k, _ in o.value or ()} == {0, 1, 2}
    assert want == {None: {"valid": True}}


def test_controls_at_the_cells_own_files(full_size):
    """Crashed ops read as failed lose the crashed writes that took effect,
    a frontier of one dies out: each answers ``false`` on the cell's valid
    history, so ``correct`` has an upper reading, 1 of 1, by
    ``verdict_mismatches`` alone (PERF.md section 2)."""
    cell, gen, want = full_size
    analyzers = cell.config["device_analyzers"]
    sound = as_program_result(want, False, analyzers[-1], 7)
    assert correct.compare([sound], want, False, 7, analyzers)["correct"]
    for control in ("info_as_fail", "beam"):
        got = reference_verdicts(cell, gen, **CONTROLS[control])
        assert got[None]["valid"] is False
        verdict = correct.compare(
            [as_program_result(got, False, analyzers[-1], 7)], want, False,
            7, analyzers)
        assert verdict["correct"] is False
        assert [k for k, c in verdict["compared"].items()
                if c["value"] > c["limit"]] == ["verdict_mismatches"]
        assert verdict["compared"]["verdict_mismatches"]["value"] == 1
        assert (verdict["attempted"], verdict["failed"]) == (1, 1)


def test_a_fission_answer_is_a_host_answer_here(full_size):
    """The configuration lists ``wgl-tpu`` alone: a verdict that came back
    from the split (``analyzer: wgl-tpu-fission``) is right and still not
    correct, by ``host_answers``."""
    cell, _, want = full_size
    analyzers = cell.config["device_analyzers"]
    assert analyzers == ["wgl-tpu"]
    split = {"valid": True, "analyzer": "wgl-tpu-fission",
             "configs-explored": 7, "fission": {"mode": "ghost"}}
    verdict = correct.compare([split], want, False, 7, analyzers)
    assert verdict["correct"] is False and verdict["failed"] == 0
    assert [k for k, c in verdict["compared"].items()
            if c["value"] > c["limit"]] == ["host_answers"]
    assert verdict["compared"]["host_answers"]["value"] == 1
    assert correct.compare([split], want, False, 7,
                           analyzers + ["wgl-tpu-fission"])["correct"]
