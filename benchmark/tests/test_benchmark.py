"""The benchmark's own tests: CPU, toy sizes.

  JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

They live here and not under ``tests/`` because a benchmark PR adds files
only under the benchmark's own directory; the repo's tier-1 command does
not collect them.
"""

from __future__ import annotations

import json
import os
import random
import re
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (HERE, ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from controls import CONTROLS, as_program_result  # noqa: E402
from gen import histories as H  # noqa: E402
from harness import correct, report, trace as tr  # noqa: E402
from harness.manifest import Cell, manifest, plugin  # noqa: E402
from harness.window import run_window  # noqa: E402
from reference import wgl_register  # noqa: E402

MAN = manifest()
CELLS = [w["name"] for w in MAN["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# -- the manifest and the files it names ------------------------------------

@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = Cell(name, MAN)
    assert cell.chips in (1, 4)
    assert cell.traffic["generator"] in H.GENERATORS
    assert callable(plugin("harness.loops", cell.traffic["loop"], "run"))
    assert callable(plugin("reference", cell.config["reference"], "check"))
    e2e = {m["name"] for m in cell.end_to_end()}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = cell.per_layer()
    assert layer
    for m in layer:
        assert m["moves"] in e2e
        assert callable(plugin("readers", m["reader"], "read"))


@pytest.mark.parametrize("m", MAN["per_layer"], ids=lambda m: m["name"])
def test_layer_file_agrees_with_manifest(m):
    with open(os.path.join(BENCH, "layers", m["name"] + ".json")) as f:
        spec = json.load(f)
    for k in ("name", "layer", "unit", "moves"):
        assert spec[k] == m[k]
    assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_names_units_and_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in MAN[g]]
    names += [w["traffic"] for w in MAN["workloads"]]
    assert all(NAME.match(n) for n in names)
    for g in ("end_to_end", "per_layer"):
        assert len({m["name"] for m in MAN[g]}) == len(MAN[g])
        for m in MAN[g]:
            assert UNIT.match(m["unit"]) and m["better"] in ("lower",
                                                             "higher")
    for c in MAN["configs"]:
        assert c["file"].startswith(MAN["paths"][0] + "/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    assert all(0.01 <= m["bound"] <= 0.25 for m in MAN["end_to_end"])
    assert len(json.dumps(MAN)) < 64 * 1024


def test_one_measure_of_speed_to_a_cell():
    """Besides ``setup_s`` a cell reports one end-to-end metric: no rate
    beside a time, no reciprocal."""
    for name in CELLS:
        assert len(Cell(name, MAN).end_to_end()) == 2


def test_unknown_device_kind_is_an_error():
    from harness.manifest import ManifestError, peaks
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ManifestError):
        peaks("TPU v99")


# -- the command -----------------------------------------------------------

def test_run_refuses_to_start_off_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert "no TPU" in p.stderr


def test_result_line_has_the_contracts_keys():
    compared = {"verdict_mismatches": {"value": 0, "limit": 0, "ok": True}}
    line = json.loads(report.result_line(
        True, 4, 0, {"verdict_s": {"value": 8.1, "unit": "s"}},
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
         "memory_peak_bytes": 1}, compared))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    traced = json.loads(report.result_line(
        True, 4, 0, {}, {}, compared, {"device_ops": [], "idle_gaps": []}))
    assert list(traced) == ["correct", "attempted", "failed", "metrics",
                            "device", "breakdown", "compared"]


# -- the window ------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


@pytest.mark.parametrize("costs,seconds,calls,wall", [
    ([2.0] * 9, 5.0, 3, 6.0),              # the call in flight finishes
    ([2.0, 7.0, 2.0], 5.0, 2, 9.0),        # a stall moves the number
    ([9.0], 5.0, 1, 9.0),                  # at least one call
    ([1.0] * 5, 5.0, 5, 5.0),              # ends exactly at the limit
])
def test_window_is_wall_over_calls(costs, seconds, calls, wall):
    clock = FakeClock()
    it = iter(costs)

    def call():
        clock.t += next(it)
        return "verdict"
    w = run_window(call, seconds, clock)
    assert (w["calls"], w["wall_s"]) == (calls, wall)
    assert w["per_call_s"] == wall / calls
    assert w["call_walls_s"] == costs[:calls]
    assert w["results"] == ["verdict"] * calls


# -- the trace reduction ---------------------------------------------------

def synthetic_trace():
    us = 1000.0
    ops = [("fusion.1", 0 * us, 10 * us),
           ("while.3", 20 * us, 40 * us),      # holds the two below
           ("sort.16", 22 * us, 20 * us),
           ("fusion.2", 45 * us, 10 * us),
           ("sort.31", 70 * us, 10 * us),
           ("copy.9", 150 * us, 10 * us)]      # outside the window
    modules = [("jit_run_chunk", 0, 60 * us), ("jit_run_chunk", 70 * us, us),
               ("jit_other", 150 * us, us)]
    spans = [(tr.WINDOW_SPAN, 0, 100 * us), ("bench:call", 0, 100 * us),
             ("bench:check_batch", 55 * us, 30 * us)]
    return tr.DeviceTrace([ops], [modules], spans)


def test_trace_busy_idle_and_shares():
    assert tr.op_name("%sort.16 = (s32[8]{0}, u32[8]{0}) sort(s32[8]{0} "
                      "%reshape.4), dimensions={0}") == "sort.16"
    assert tr.op_name("fusion.2") == "fusion.2"
    t = synthetic_trace()
    assert t.window_s == pytest.approx(100e-6)
    assert t.busy_s == pytest.approx(60e-6)        # 10 + 40 + 10
    assert t.launches == 2
    assert t.op_self_s["while.3"] == pytest.approx(10e-6)
    assert t.op_self_s["sort.16"] == pytest.approx(20e-6)
    assert "copy.9" not in t.op_self_s
    ctx = {"trace": t, "counters": {"calls": 1, "configs": 600}}
    assert plugin("readers", "trace_busy", "read")(
        ctx, what="idle_share") == pytest.approx(40.0)
    assert plugin("readers", "trace_op_share", "read")(
        ctx, prefix="sort") == pytest.approx(50.0)
    assert plugin("readers", "trace_busy", "read")(
        ctx, what="per_busy_s", counter="configs") == pytest.approx(1e7)
    assert plugin("readers", "trace_launches", "read")(ctx) == 2
    gaps = dict((k, v) for k, v in t.idle_gaps())
    # a gap goes to the innermost span that holds its middle
    assert gaps["sum:call"] == pytest.approx(30e-6)          # 10-20, 80-100
    assert gaps["sum:check_batch"] == pytest.approx(10e-6)   # 60-70
    assert t.top_ops(1)[0][0] in ("sort.16",)


def test_readers_return_nothing_when_there_is_nothing_to_read():
    empty = tr.DeviceTrace([[]], [[]], [])
    ctx = {"trace": empty, "counters": {"calls": 1}, "spans": {},
           "window_s": 1.0, "memory": {}, "events": {}, "probes": {}}
    for reader, args in (("trace_busy", {"what": "idle_share"}),
                         ("trace_op_share", {"prefix": "sort"}),
                         ("trace_launches", {}),
                         ("span_share", {"span": "check_batch"}),
                         ("memory_stats", {"key": "peak_bytes_in_use"}),
                         ("monitoring_events", {"phase": "window",
                                                "event": "cache_misses"}),
                         ("host_timer", {"probe": "split"}),
                         ("counter", {"name": "nope"})):
        assert plugin("readers", reader, "read")(ctx, **args) is None


# -- the generators and the reference ----------------------------------------

def as_program(recs):
    from harness.loops.offline import program_history
    return program_history(recs)


@pytest.mark.parametrize("seed", [0, 11, 2026])
def test_generators_are_synths(seed):
    from jepsen_tpu import synth
    from jepsen_tpu.history import History
    kw = dict(concurrency=8, crash_p=0.01, seed=seed)
    mine = H.cas_register_history(300, **kw)
    theirs = synth.cas_register_history(300, **kw)
    assert as_program(mine).ops == History(list(theirs), reindex=True).ops
    assert as_program(H.corrupt_reads(mine, n=2, seed=seed, within=0.5)).ops \
        == synth.corrupt_reads(theirs, n=2, seed=seed, within=0.5).ops
    assert as_program(H.doomed_cas_padding(5)).ops \
        == History(synth.doomed_cas_padding(5), reindex=True).ops


def test_keyed_lanes_are_chip_smokes():
    import chip_smoke
    theirs = chip_smoke.keyed_lanes(0, 8, 40)
    config = {"values": 5, "keys": 8, "ops_per_key": 40,
              "processes_per_key": 6, "read_p": 0.5, "write_p": 0.25}
    params = {"history_seed": 100, "crash_p": 0.005, "refute_every": 4,
              "concurrent_keys": 3, "process_stride": 10}
    gen = H.keyed_registers(config, params, seed=7)
    mine = H.split_keys(gen["records"])
    assert len(mine) == 8
    # the same multiset of lanes up to the seed's relabeling: same shapes
    shape = lambda recs: [(o.type, o.f) for o in recs]  # noqa: E731
    assert sorted(shape(v) for v in mine.values()) \
        == sorted(shape(list(h)) for h in theirs)
    bad = [k for k, v in mine.items()
           if any(isinstance(o.value, int) and o.value > 1000 for o in v)]
    assert len(bad) == 2


@pytest.mark.parametrize("seed", [1, 2**31 + 7, 3_000_000_019])
def test_seed_relabels_and_keeps_the_search(seed):
    base = H.doomed_cas_padding(3) + H.corrupt_reads(
        H.cas_register_history(200, concurrency=6, crash_p=0.02, seed=5),
        n=1, seed=5)
    a = H.relabel(base, random.Random(seed), 5)
    assert a == H.relabel(base, random.Random(seed), 5)
    assert a != H.relabel(base, random.Random(seed + 1), 5)
    assert [(o.type, o.f) for o in a] == [(o.type, o.f) for o in base]
    assert wgl_register.check(a) == wgl_register.check(base)


@pytest.mark.parametrize("seed", range(6))
def test_reference_agrees_with_the_programs_host_oracle(seed):
    from jepsen_tpu.checker import wgl_cpu
    from jepsen_tpu.models import get_model
    h = H.cas_register_history(80 + 10 * seed, concurrency=6, crash_p=0.03,
                               seed=seed)
    if seed % 2:
        h = H.corrupt_reads(h, n=1, seed=seed)
    if seed % 3 == 0:
        h = H.doomed_cas_padding(4) + h
    want = wgl_cpu.check(get_model("cas-register").cpu_model(),
                         as_program(h))
    got = wgl_register.check(h)
    assert got["valid"] == want["valid"]
    if not got["valid"]:
        assert got["op_index"] == want["op"]["index"]


# -- a run end to end at toy size, sound and broken ---------------------------

def toy_cell(name):
    cell = Cell(name, MAN)
    if "keys" in cell.config:
        cell.config.update(keys=8, ops_per_key=40)
        cell.traffic["params"].update(refute_every=5, concurrent_keys=3)
    else:
        cell.config.update(ops=120)
        if cell.traffic["params"]["doomed_cas"]:
            cell.traffic["params"]["doomed_cas"] = 4
    return cell


def fake_chip(chips):
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def toy_run(name, capsys, traced=False, seed=2**31 + 11):
    from harness.loops import offline
    rc = offline.run(toy_cell(name), seed, 0.2, traced, time.monotonic(),
                     report.Log(), require_chip=fake_chip)
    out, err = capsys.readouterr()
    assert rc == 0
    line = json.loads(out.splitlines()[-1])
    assert err.splitlines()[-1].startswith("compared ")
    return line


@pytest.mark.parametrize("name", CELLS)
def test_toy_run_is_correct(name, capsys):
    line = toy_run(name, capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {Cell(name).traffic["verdict_metric"],
                                    "setup_s"}
    assert line["attempted"] >= 1
    assert list(line)[-1] == "compared"
    assert all(c["value"] == 0 for c in line["compared"].values())


def test_toy_traced_run_reports_layer_metrics(capsys):
    line = toy_run("keyed200.offline", capsys, traced=True)
    assert line["correct"] is True
    assert {"entry.host_answers.keyed", "entry.split_s",
            "prepare.s_per_kop.keyed", "drivers.batch_share",
            "compile.window_compiles.keyed"} <= set(line["metrics"])
    assert "keyed_verdict_s" not in line["metrics"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    # on the CPU no device plane is read: nothing, never a 0 share
    assert "device.idle_share.keyed" not in line["metrics"]


def test_fault_answer_altered_where_it_is_produced(capsys, monkeypatch):
    from jepsen_tpu.checker import wgl_tpu
    inner = wgl_tpu.check

    def altered(*a, **kw):
        res = inner(*a, **kw)
        if res.get("valid") is True:
            res = dict(res, valid=False, op={"index": 0})
        return res
    monkeypatch.setattr(wgl_tpu, "check", altered)
    line = toy_run("cas10k-clean.offline", capsys)
    assert line["correct"] is False
    assert line["compared"]["verdict_mismatches"]["value"] >= 1
    assert line["failed"] >= 1


def test_fault_half_of_the_batch_left_out(capsys, monkeypatch):
    import jepsen_tpu.parallel as par
    inner = par.check_batch

    def half(model, histories, **kw):
        n = len(histories) // 2
        rest = [{"valid": True, "analyzer": "wgl-tpu-batch",
                 "configs-explored": 0}] * (len(histories) - n)
        return inner(model, histories[:n], **kw) + rest
    monkeypatch.setattr(par, "check_batch", half)
    line = toy_run("keyed200.offline", capsys)
    assert line["correct"] is False
    assert line["compared"]["verdict_mismatches"]["value"] >= 1


def test_fault_unknown_counts_as_failed(capsys, monkeypatch):
    from jepsen_tpu.checker import wgl_tpu
    monkeypatch.setattr(
        wgl_tpu, "check",
        lambda *a, **kw: {"valid": "unknown", "analyzer": "wgl-tpu",
                          "error": "configuration capacity exceeded"})
    line = toy_run("cas10k-crash.offline", capsys)
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] >= 1


def test_fault_host_fallback_is_not_a_device_answer(capsys, monkeypatch):
    from jepsen_tpu.checker import wgl_tpu

    def crash(*a, **kw):
        raise RuntimeError("device lost")
    monkeypatch.setattr(wgl_tpu, "check", crash)
    line = toy_run("cas10k-clean.offline", capsys)
    assert line["correct"] is False
    assert line["compared"]["host_answers"]["value"] >= 1
    assert line["compared"]["verdict_mismatches"]["value"] == 0


# -- the control ------------------------------------------------------------

@pytest.mark.parametrize("control", sorted(CONTROLS))
@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name, control):
    """The reference with a guarantee broken, in the program's place, on
    three seeds at a toy size of the cell's own structure."""
    from harness.loops.offline import reference_verdicts
    cell = toy_cell(name)
    if "keys" in cell.config:
        cell.config.update(keys=64)
        cell.traffic["params"].update(crash_p=0.05)
    else:
        cell.config.update(ops=400)
        cell.traffic["params"].update(crash_p=0.04)
    analyzers = cell.config["device_analyzers"]
    for seed in (3, 2**31 + 1, 3_000_000_001):
        gen = H.GENERATORS[cell.traffic["generator"]](
            cell.config, cell.traffic["params"], seed)
        want = reference_verdicts(cell, gen)
        sound = as_program_result(want, gen["keyed"], analyzers[-1], 7)
        assert correct.compare([sound], want, gen["keyed"], 7,
                               analyzers)["correct"] is True
        broken = as_program_result(
            reference_verdicts(cell, gen, **CONTROLS[control]),
            gen["keyed"], analyzers[-1], 7)
        got = correct.compare([broken], want, gen["keyed"], 7, analyzers)
        assert got["correct"] is False
        assert got["compared"]["verdict_mismatches"]["value"] >= 1
