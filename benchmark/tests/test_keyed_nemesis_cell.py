"""The ``keyed-register-nemesis`` configuration, its cell and the refuted
cell of ``keyed-register-200`` (PR 27): the files the manifest names (what
``test_benchmark.py`` checks of every cell it finds, plus what is particular
to these), and the control at a small size and at the cell's own.  CPU, no
chip:

  JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

from __future__ import annotations

import json
import os

import pytest

from controls import CONTROLS, as_program_result
from gen import histories as H
from gen import nemesis_keyed as N
from harness import correct
from harness.loops.offline import reference_verdicts
from harness.manifest import Cell, manifest, plugin

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAN = manifest()
NEW_CELL = "keyed-nemesis.offline"
REFUTED_CELL = "keyed200-refuted.offline"
NEW_LAYERS = ["drivers.passes_per_call.keyed",
              "drivers.lane_retries_per_call.keyed",
              "drivers.lane_fill.keyed"]


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def cells():
    return [w["name"] for w in MAN["workloads"]]


# -- the manifest and the files it names ------------------------------------

@pytest.mark.parametrize("name", [NEW_CELL, REFUTED_CELL])
def test_new_cell_reports_what_the_keyed_cell_reports(name):
    cell, old = Cell(name, MAN), Cell("keyed200.offline", MAN)
    assert cell.chips == 1 and len(cell.entry["why"]) <= 200
    assert {m["name"] for m in cell.end_to_end()} == {"keyed_verdict_s",
                                                      "setup_s"}
    mine = {m["name"] for m in cell.per_layer()}
    # all of the keyed cell's but the probe of a path the checker left
    assert mine == {m["name"] for m in old.per_layer()} - {"entry.split_s"}
    assert set(NEW_LAYERS) <= mine
    assert cell.traffic["verdict_metric"] == "keyed_verdict_s"
    assert cell.traffic["spans"] == old.traffic["spans"]
    assert cell.traffic["trace_seconds"] == old.traffic["trace_seconds"]
    assert callable(plugin("harness.loops", cell.traffic["loop"], "run"))
    assert cell.traffic["generator"] in H.GENERATORS


def test_manifest_only_gained_entries_at_the_end():
    assert [c["name"] for c in MAN["configs"]][:2] == [
        "cas-register-10k", "keyed-register-200"]
    assert cells() == ["cas10k-crash.offline", "keyed200.offline",
                       "cas10k-clean.offline", NEW_CELL, REFUTED_CELL]
    assert [m["name"] for m in MAN["per_layer"]][-3:] == NEW_LAYERS
    for m in MAN["per_layer"][-3:]:
        spec = load("layers", m["name"])
        assert {k: spec[k] for k in ("name", "layer", "unit", "moves")} == \
            {k: m[k] for k in ("name", "layer", "unit", "moves")}
        assert m["layer"] == "drivers" and m["moves"] == "keyed_verdict_s"
        assert m["workloads"] == ["keyed200.offline", NEW_CELL, REFUTED_CELL]
        assert callable(plugin("readers", spec["reader"], "read"))
    split = next(m for m in MAN["per_layer"] if m["name"] == "entry.split_s")
    assert split["workloads"] == ["keyed200.offline"]
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) == 0


def test_the_configuration_states_the_sources_shapes():
    entry = next(c for c in MAN["configs"]
                 if c["name"] == "keyed-register-nemesis")
    config, old = load("configs", "keyed-register-nemesis"), \
        load("configs", "keyed-register-200")
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["source"] != next(
        c for c in MAN["configs"] if c["name"] == "keyed-register-200"
    )["source"]
    assert entry["reduced"] == config["reduced"] == ["keys"]
    assert "74.18 s" in config["reduced_from"]["keys"]
    assert config["architecture"] is None
    assert (config["nodes"], config["threads_per_key"], config["values"],
            config["per_key_limit"], config["process_limit"],
            config["client_timeout_s"], config["keys"]) == (
                5, 10, 5, 200, 20, 5, 256)
    assert config["per_key_limit_factor"] == [0.9, 1.0]
    assert (config["timed_out_read"], config["timed_out_write_or_cas"]) == (
        "fail", "info")
    assert config["guarantees"] == old["guarantees"]
    assert config["device_analyzers"] == ["wgl-tpu", "wgl-tpu-batch"]
    assert (config["model"], config["reference"]) == ("cas-register",
                                                      "wgl_register")
    assert {"keys", "concurrent_keys", "per_key_limit", "nemesis",
            "process_limit"} <= set(config["assumed"])


def test_the_traffic_file():
    nem = load("traffic", "offline-keyed-nemesis")
    assert (nem["loop"], nem["generator_module"], nem["generator"]) == (
        "offline_plug", "nemesis_keyed", "keyed_nemesis")
    assert nem["params"] == {
        "history_seed": 100, "partition_block": 32, "minority_nodes": 2,
        "timeout_ops": 12, "crash_apply_p": 0.5, "crash_p": 0.005,
        "refute_every": 64, "concurrent_keys": 8, "process_stride": 20}


def test_the_refuted_cell_is_the_keyed_cell_with_128_keys_refuted():
    cell, old = Cell(REFUTED_CELL, MAN), Cell("keyed200.offline", MAN)
    assert cell.entry["config"] == old.entry["config"]
    assert cell.config == old.config
    assert dict(cell.traffic["params"], refute_every=64) == \
        old.traffic["params"]
    assert cell.traffic["loop"] == old.traffic["loop"] == "offline"
    gen = H.GENERATORS[cell.traffic["generator"]](
        cell.config, cell.traffic["params"], 2**31 + 9)
    want = reference_verdicts(cell, gen)
    assert len(want) == 512
    assert sum(not w["valid"] for w in want.values()) == 128


# -- the generator at the cell's own size ------------------------------------

def test_full_size_lanes_are_what_perf_md_says(capsys):
    cell = Cell(NEW_CELL, MAN)
    gen = H.GENERATORS["keyed_nemesis"](cell.config, cell.traffic["params"],
                                        2**31 + 3)
    lanes = H.split_keys(gen["records"])
    assert len(lanes) == 256
    stats = [N.lane_stats(v) for v in lanes.values()]
    assert 48_000 < sum(s[0] for s in stats) < 49_000
    assert all(180 <= s[0] <= 200 for s in stats)
    partitioned = [s for v, s in zip(lanes.values(), stats)
                   if any(o.error == "timeout" for o in v)]
    assert len(partitioned) == 128
    assert min(s[1] for s in partitioned) >= 5
    assert max(s[2] for s in stats) <= 16       # the engine's window
    assert max(s[3] for s in stats) <= 20       # process_limit
    corrupted = [k for k, v in lanes.items()
                 if any(isinstance(o.value, int) and o.value >= 1000
                        for o in v)]
    assert len(corrupted) == 4
    assert "keys cut by process_limit 20" in capsys.readouterr().out


# -- the control -------------------------------------------------------------

def small_cell():
    cell = Cell(NEW_CELL, MAN)
    cell.config.update(keys=24, per_key_limit=60)
    cell.traffic["params"].update(partition_block=4, refute_every=8,
                                  timeout_ops=2)
    return cell


@pytest.mark.parametrize("seed", [3, 2**31 + 1, 3_000_000_001])
def test_control_mis_answers_a_known_number_of_keys(seed):
    """24 keys of 60 ops, 12 of them partitioned, 3 refuted: crashed ops
    read as failed lose the late effects of timed-out writes, so 7 keys
    (6 of the 12 partitioned, 1 healed) come out refuted or refuted at
    another op; a frontier of one answers all 24 ``false``.  The same on
    every seed, because the seed only relabels."""
    cell = small_cell()
    analyzers = cell.config["device_analyzers"]
    gen = H.GENERATORS["keyed_nemesis"](cell.config, cell.traffic["params"],
                                        seed)
    want = reference_verdicts(cell, gen)
    assert sum(not w["valid"] for w in want.values()) == 3
    sound = as_program_result(want, True, analyzers[-1], 7)
    assert correct.compare([sound], want, True, 7, analyzers)["correct"]
    lanes = H.split_keys(gen["records"])
    partitioned = {k for k, v in lanes.items()
                   if any(o.error == "timeout" for o in v)}
    for control, n_wrong, n_partitioned in (("info_as_fail", 7, 6),
                                            ("beam", 24, 12)):
        got = reference_verdicts(cell, gen, **CONTROLS[control])
        wrong = {k for k in want if got[k] != want[k]}
        assert (len(wrong), len(wrong & partitioned)) == (n_wrong,
                                                          n_partitioned)
        verdict = correct.compare(
            [as_program_result(got, True, analyzers[-1], 7)], want, True, 7,
            analyzers)
        assert verdict["correct"] is False
        assert verdict["compared"]["verdict_mismatches"]["value"] == n_wrong
        assert verdict["failed"] == n_wrong


def test_control_at_the_cells_own_size():
    """The cell's own files, 256 keys of 180-200 ops: crashed ops read as
    failed mis-answer 97 keys (81 of the 128 partitioned, 16 healed; 96
    verdicts flip and one key is refuted at another op), a frontier of one
    all 256; each comes out as not correct by ``verdict_mismatches`` alone
    (PERF.md section 2)."""
    cell = Cell(NEW_CELL, MAN)
    analyzers = cell.config["device_analyzers"]
    gen = H.GENERATORS["keyed_nemesis"](cell.config, cell.traffic["params"],
                                        2**31 + 11)
    want = reference_verdicts(cell, gen)
    assert (len(want), sum(not w["valid"] for w in want.values())) == (256, 4)
    lanes = H.split_keys(gen["records"])
    partitioned = {k for k, v in lanes.items()
                   if any(o.error == "timeout" for o in v)}
    for control, n_wrong, n_partitioned, n_flipped in (
            ("info_as_fail", 97, 81, 96), ("beam", 256, 128, 252)):
        got = reference_verdicts(cell, gen, **CONTROLS[control])
        wrong = {k for k in want if got[k] != want[k]}
        flipped = {k for k in want if got[k]["valid"] != want[k]["valid"]}
        assert (len(wrong), len(wrong & partitioned), len(flipped)) == (
            n_wrong, n_partitioned, n_flipped)
        verdict = correct.compare(
            [as_program_result(got, True, analyzers[-1], 7)], want, True, 7,
            analyzers)
        assert verdict["correct"] is False
        assert [k for k, c in verdict["compared"].items()
                if c["value"] > c["limit"]] == ["verdict_mismatches"]
        assert verdict["failed"] == n_wrong
