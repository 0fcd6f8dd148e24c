"""The ``multireg-10k-10thread`` configuration and its cell
``multireg-4chip.offline`` (PR 33), the first on four chips: the manifest's
entries and the files they name.  What the program does with such a history
at a small size is ``tests/test_multireg_sharded.py``'s.  CPU, no chip:

  JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

from __future__ import annotations

import json
import os

from harness.manifest import Cell, manifest, plugin

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAN = manifest()
NEW_CELL, ITS_TWIN = "multireg-4chip.offline", "multireg10k.offline"
NEW_LAYERS = ["drivers.shard_share", "kernels.all_gather_share",
              "drivers.shard_balance"]


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def test_the_cell_takes_four_chips_and_says_why_in_200_characters():
    cell = Cell(NEW_CELL, MAN)
    assert cell.chips == 4
    assert 0 < len(cell.entry["why"]) <= 200
    for word in ("10 threads", "41,232", "shard", "HBM"):
        assert word in cell.entry["why"], word
    assert (cell.entry["config"], cell.entry["traffic"]) == (
        "multireg-10k-10thread", "offline-multireg-10thread")
    # one cell of seven: under the half that may ask for four chips
    four = [w["name"] for w in MAN["workloads"] if w["chips"] == 4]
    assert four == [NEW_CELL] and len(four) <= len(MAN["workloads"]) // 2


def test_the_configurations_source_fits_and_is_its_own():
    entry = next(c for c in MAN["configs"]
                 if c["name"] == "multireg-10k-10thread")
    config = load("configs", "multireg-10k-10thread")
    assert entry["source"] == config["source"]
    assert 0 < len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert len({c["source"] for c in MAN["configs"]}) == len(MAN["configs"])
    assert entry["file"] == "benchmark/configs/multireg-10k-10thread.json"
    assert entry["reduced"] == config["reduced"] == []
    assert config["architecture"] is None and config["concurrency"] == 10
    assert config["device_analyzers"] == ["wgl-tpu-sharded"]
    assert config["chips"].startswith("4;")


def test_the_manifest_gained_the_cell_at_the_end_of_its_lists():
    assert MAN["configs"][-1]["name"] == "multireg-10k-10thread"
    assert MAN["workloads"][-1]["name"] == NEW_CELL
    assert [m["name"] for m in MAN["per_layer"]][-3:] == NEW_LAYERS
    gained = 0
    for group in ("end_to_end", "per_layer"):
        for m in MAN[group]:
            listed = m.get("workloads")
            if m["name"] in NEW_LAYERS:
                assert listed == [NEW_CELL]
            elif listed and ITS_TWIN in listed:
                assert listed[-1] == NEW_CELL and listed.count(NEW_CELL) == 1
                gained += 1
            elif listed:
                assert NEW_CELL not in listed, m["name"]
    assert gained == 18         # verdict_s and the twin's 17 per-layer metrics


def test_the_cell_reports_its_twins_metrics_and_three_of_its_own():
    cell, twin = Cell(NEW_CELL, MAN), Cell(ITS_TWIN, MAN)
    assert {m["name"] for m in cell.end_to_end()} == {"verdict_s", "setup_s"}
    mine = {m["name"] for m in cell.per_layer()}
    assert mine == {m["name"] for m in twin.per_layer()} | set(NEW_LAYERS)
    for m in MAN["per_layer"][-3:]:
        spec = load("layers", m["name"])
        assert {k: spec[k] for k in ("name", "layer", "unit", "moves")} == \
            {k: m[k] for k in ("name", "layer", "unit", "moves")}
        assert (m["moves"], m["unit"]) == ("verdict_s", "%")
        assert callable(plugin("readers", spec["reader"], "read"))


def test_the_traffic_is_the_twins_but_for_the_loop_and_the_span():
    new, old = load("traffic", "offline-multireg-10thread"), \
        load("traffic", "offline-multireg")
    same = ("entry", "generator_module", "generator", "params",
            "trace_seconds", "verdict_metric")
    assert {k: new[k] for k in same} == {k: old[k] for k in same}
    assert new["loop"] == "offline_requires" and old["loop"] == "offline_plug"
    assert callable(plugin("harness.loops", new["loop"], "run"))
    assert new["spans"] == {
        "check_sharded": "jepsen_tpu.parallel.sharded:check_sharded"}
    assert new["requires"] == ["jepsen_tpu.engine.fission:attached_chips"]
