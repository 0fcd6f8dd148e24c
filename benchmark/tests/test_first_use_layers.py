"""PR 35's additions: the ``program_sums`` reader and the six ``setup_s``
layer metrics that read the program's ``first_use_stats()``.

  JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -k first_use

(The reader's arithmetic on a hand-made dict and each layer file against
the program's keys are checked in tier-1, ``tests/test_first_use.py``.)
"""

from __future__ import annotations

import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (HERE, ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import report  # noqa: E402
from harness.manifest import Cell, manifest  # noqa: E402
from readers import program_sums  # noqa: E402
from test_benchmark import fake_chip, toy_cell  # noqa: E402

MAN = manifest()
NEW_LAYERS = ["setup.warmup_excess_s", "compile.trace_s", "compile.lower_s",
              "compile.load_s", "compile.eager_s", "setup.warmup_unnamed_s"]


def test_manifest_tail_is_the_six_and_every_cell_reports_them():
    assert [m["name"] for m in MAN["per_layer"]][-6:] == NEW_LAYERS
    cells = [w["name"] for w in MAN["workloads"]]
    for m in MAN["per_layer"][-6:]:
        assert m["workloads"] == cells and m["moves"] == "setup_s"
        assert (m["layer"], m["source"], m["better"], m["unit"]) == (
            "compile", "program_counter", "lower", "s")
    for name in cells:
        assert set(NEW_LAYERS) <= {m["name"] for m in Cell(name).per_layer()}


def test_a_program_without_the_counter_reads_nothing():
    # the parent commit under these files: no such function, no metric
    assert program_sums.read({}, "jepsen_tpu.obs.hist:no_such_stats",
                             ["trace_s"]) is None
    assert program_sums.read({}, "jepsen_tpu.no_such_module:stats",
                             ["trace_s"]) is None


@pytest.mark.parametrize("name", ["cas10k-clean.offline",
                                  "keyed200.offline"])
def test_toy_traced_run_reports_the_six(name, capsys):
    from harness.loops import offline
    from jepsen_tpu.obs.hist import first_use_stats, reset_first_use_stats
    reset_first_use_stats()
    rc = offline.run(toy_cell(name), 2**31 + 35, 0.2, True, time.monotonic(),
                     report.Log(), require_chip=fake_chip)
    out, _ = capsys.readouterr()
    assert rc == 0
    metrics = json.loads(out.splitlines()[-1])["metrics"]
    assert set(NEW_LAYERS) <= set(metrics)
    got = {k: metrics[k]["value"] for k in NEW_LAYERS}
    sums = first_use_stats()
    assert got["compile.trace_s"] == sums["trace_s"]
    assert got["compile.eager_s"] == sums["eager_s"]
    assert got["setup.warmup_unnamed_s"] == pytest.approx(
        got["setup.warmup_excess_s"] - sums["trace_s"] - sums["lower_s"]
        - sums["load_s"])
    # the same JAX event through the harness's own listener
    log = [ln for ln in out.splitlines() if "set-up breakdown" in ln][0]
    harness_load = float(log.split("backend_compile_or_load ")[1]
                         .split(";")[0])
    assert got["compile.load_s"] == pytest.approx(harness_load, abs=2e-3)
