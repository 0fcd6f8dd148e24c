"""The ``multireg-10k-10thread`` deployment at a small size on the CPU's
eight virtual devices: a 10-thread, 3-key register history whose frontier
outgrows a (small) fission threshold goes on, through
``linearizable(multi-register).check``, with its frontier sharded over the
devices the check is handed, from the one-device search's snapshot, and
answers as the benchmark's plain reference, the host oracle and one big
device do; a refutation carries the refuting op and a host-confirmed
witness; with one device nothing changes; every degraded path is
``unknown`` or the fallback chain, never ``false``; ``sharded_stats()``
and the ``drivers.shard`` span say the same; and the cell's files load.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from gen import multi_register as M  # noqa: E402
from harness.loops import offline_requires  # noqa: E402
from harness.manifest import Cell, manifest, plugin  # noqa: E402
from reference import wgl_multi_register  # noqa: E402

from jepsen_tpu import core, synth  # noqa: E402
from jepsen_tpu.checker import wgl_cpu, wgl_tpu  # noqa: E402
from jepsen_tpu.checker.linearizable import linearizable  # noqa: E402
from jepsen_tpu.engine import fission  # noqa: E402
from jepsen_tpu.engine.cache import EngineCache  # noqa: E402
from jepsen_tpu.models import MultiRegister, get_model  # noqa: E402
from jepsen_tpu.obs.recorder import RECORDER  # noqa: E402
from jepsen_tpu.parallel import make_mesh, sharded  # noqa: E402

CELL = "multireg-4chip.offline"
#: one device's ladder here ends at 1,024 rows (64, 256, 1,024), and the
#: histories below peak between 1,500 and 3,900 configurations
THRESHOLD, CAPACITY, CHUNK = 1024, 64, 16
#: (history seed, shards): the frontier fits the shards' 1,024 rows each
FITS = [(3, 2), (4, 2), (9, 2), (0, 4), (1, 4), (3, 4), (6, 4), (8, 4)]


@pytest.fixture(scope="module")
def model():
    return get_model("multi-register")


def history(seed, ops=40):
    """The workload's shape, a few dozen ops: 10 threads, 3 keys, values
    0-4, subsets, crashes."""
    return synth.multi_register_history(ops, keys=3, concurrency=10,
                                        crash_p=0.01, seed=seed)


def checker(model, shards, **kw):
    opts = dict(threshold=THRESHOLD, capacity=CAPACITY, chunk=CHUNK,
                shard_devices=jax.devices()[:shards])
    opts.update(kw)
    return linearizable(model, **opts)


def analyze(model, h, shards, **kw):
    return core.analyze({"checker": checker(model, shards, **kw)}, h)


@pytest.fixture
def rec():
    RECORDER.enable()
    RECORDER.clear()
    yield RECORDER
    RECORDER.disable()
    RECORDER.clear()


def spans(rec, name):
    return [e for e in rec.snapshot() if e["name"] == name]


# -- the verdict, through the normal path -------------------------------------

@pytest.mark.parametrize("seed,shards", FITS)
def test_sharded_answers_as_the_references_do(model, seed, shards):
    h = history(seed)
    res = analyze(model, h, shards)
    assert res["analyzer"] == "wgl-tpu-sharded" and res["shards"] == shards
    assert res["fission"] == {"mode": "shard", "shards": shards}
    assert "fallback-chain" not in res and "fallback" not in res
    assert res["valid"] is True
    assert wgl_multi_register.check(list(h))["valid"] is True
    assert wgl_cpu.check(MultiRegister(), h)["valid"] is True
    # the same search as one device with room for it: the count goes on
    # from the snapshot, so it is the whole history's
    one = wgl_tpu.check(model, h, capacity=4096, max_capacity=4096,
                        chunk=CHUNK)
    assert one["valid"] is True
    assert res["configs-explored"] == one["configs-explored"]
    assert THRESHOLD < res["max-capacity-reached"] <= THRESHOLD * shards


def corrupted(model, seed):
    """``history(seed)`` with one read changed to a value nobody wrote, the
    first such that one device's ladder ends before it gets that far."""
    h = history(seed)
    for cseed in range(40):
        bad = synth.corrupt_multi_reads(h, n=1, seed=cseed)
        one = wgl_tpu.check(model, bad, capacity=CAPACITY,
                            max_capacity=THRESHOLD, chunk=CHUNK)
        if one.get("capacity-exceeded"):
            want = wgl_multi_register.check(list(bad))
            assert want["valid"] is False
            return bad, want
    raise AssertionError("no corrupted read past the hand-over found")


@pytest.mark.parametrize("seed,shards", [(3, 2), (9, 2), (3, 4), (8, 4)])
def test_sharded_refutes_with_the_op_and_a_host_witness(model, seed, shards):
    bad, want = corrupted(model, seed)
    res = analyze(model, bad, shards)
    assert res["analyzer"] == "wgl-tpu-sharded"
    assert res["valid"] is False
    cpu = wgl_cpu.check(MultiRegister(), bad)
    assert cpu["valid"] is False
    assert res["op"]["index"] == cpu["op"]["index"] == want["op_index"]
    assert res["witness"]["valid"] is False
    assert res["witness"]["analyzer"] == "wgl-cpu"
    assert "fallback-chain" not in res


@pytest.mark.parametrize("devices", [None, (), "one"])
def test_with_one_device_the_parents_path_is_taken(model, devices, rec):
    """None is what the CPU backend has attached (``attached_chips``: no
    accelerator, so nothing to shard over, whatever the virtual device
    count); an empty hand and one device are the same."""
    h = history(3)
    devs = jax.devices()[:1] if devices == "one" else devices
    res = fission.check(model, h, threshold=THRESHOLD, capacity=CAPACITY,
                        chunk=CHUNK, shard_devices=devs)
    want = fission.split_check(model, h, threshold=THRESHOLD,
                               capacity=CAPACITY, chunk=CHUNK,
                               shard_devices=())
    assert res["analyzer"] == want["analyzer"] != "wgl-tpu-sharded"
    assert res["valid"] is want["valid"] is True
    assert res["fission"]["mode"] == want["fission"]["mode"] != "shard"
    assert not spans(rec, "drivers.shard")
    assert not spans(rec, "drivers.shard_handover")


def test_attached_chips_counts_no_cpu_device():
    assert jax.devices()[0].platform == "cpu" and len(jax.devices()) == 8
    assert fission.attached_chips() == []


# -- degraded paths: unknown or the fallback chain, never false --------------

@pytest.mark.parametrize("seed,shards", [(0, 2), (8, 2), (2, 4), (5, 4)])
def test_overflow_at_the_sharded_ceiling_is_unknown(model, seed, shards):
    """Frontiers of 2,684 and 3,888 over 2 x 1,024 rows, of over 4,096
    over 4 x 1,024."""
    res = analyze(model, history(seed), shards)
    assert res["valid"] == "unknown"
    assert res["analyzer"] == "wgl-tpu-sharded"
    assert res["capacity-exceeded"] is True
    assert res["max-capacity-reached"] == THRESHOLD * shards
    assert f"{THRESHOLD}x{shards}" in res["error"]
    assert "op" not in res and "fallback-chain" not in res


@pytest.mark.parametrize("refuted", [False, True])
def test_an_exception_in_the_sharded_run_ends_in_the_fallback_chain(
        model, refuted, monkeypatch):
    """The device failed, not the history: the host oracle answers, and the
    verdict says by which chain it came."""
    def broken(*a, **kw):
        raise RuntimeError("mesh lost")
    monkeypatch.setattr(sharded, "mesh_program", broken)
    monkeypatch.setattr(sharded, "_ENGINE_CACHE", EngineCache(4))
    h = corrupted(model, 3)[0] if refuted else history(3)
    res = analyze(model, h, 2)
    chain = res["fallback-chain"]
    assert chain[0]["solver"] == "wgl-tpu" and "mesh lost" in chain[0]["error"]
    assert res["analyzer"] == "wgl-cpu"
    assert res["valid"] is (not refuted)
    if refuted:
        assert res["op"]["index"] == wgl_cpu.check(
            MultiRegister(), h)["op"]["index"]


# -- the hand-over, the span and the counter ---------------------------------

@pytest.mark.parametrize("seed,shards", [(3, 2), (6, 4)])
def test_sharded_stats_agree_with_the_span(model, seed, shards, rec):
    sharded.reset_sharded_stats()
    assert not any(sharded.sharded_stats().values())
    res = analyze(model, history(seed), shards)
    assert res["analyzer"] == "wgl-tpu-sharded"
    handover, = spans(rec, "drivers.shard_handover")
    shard, = spans(rec, "drivers.shard")
    check, = spans(rec, "drivers.check")
    did, stats = shard["args"], sharded.sharded_stats()
    assert handover["args"]["mode"] == "resume"
    assert handover["args"]["peak"] > THRESHOLD
    # the snapshot is the chunk boundary before the overflow: what the one
    # device's polls had consumed, less the overflowed chunk's own part
    assert 0 < handover["args"]["event"] <= check["args"]["events_consumed"] \
        < handover["args"]["event"] + CHUNK
    assert stats["events_sharded"] == did["events_consumed"] > 0
    assert stats["events_total"] == \
        handover["args"]["event"] + did["events_consumed"]
    assert did["shards"] == shards
    assert did["cap_per_shard"] == did["max_capacity"] == THRESHOLD
    assert did["pauses"] == did["resumes"]
    assert did["resized"] == did["grows"] + did["shrinks"]
    assert did["dispatches"] == len([
        e for e in spans(rec, "drivers.dispatch")
        if e["ts"] >= shard["ts"]])
    assert 0 <= stats["rows_live_min"] <= stats["rows_live_max"]
    assert stats["rows_live_max"] > 0
    # and check_stats() holds both searches' events, at the global capacity
    assert check["args"]["cap_events"] + did["cap_events"] > 0
    assert did["cap_events"] == THRESHOLD * shards * did["events_consumed"]
    sharded.reset_sharded_stats()
    assert not any(sharded.sharded_stats().values())


@pytest.mark.parametrize("seed,shards", [(4, 2), (1, 4)])
def test_from_event_0_is_the_same_search(model, seed, shards, rec):
    """``check_sharded`` without a snapshot (a lane of ``check_batch``)
    climbs the ladder from the first rung, and counts what the resumed
    search counts."""
    h = history(seed)
    resumed = analyze(model, h, shards)
    rec.clear()
    res = sharded.check_sharded(
        model, h, devices=jax.devices()[:shards], capacity_per_shard=CAPACITY,
        max_capacity_per_shard=THRESHOLD, chunk=CHUNK)
    for k in ("valid", "analyzer", "configs-explored", "shards",
              "capacity"):
        assert res[k] == resumed[k], k
    shard, = spans(rec, "drivers.shard")
    assert shard["args"]["grows"] == 2 and shard["args"]["events"] > 0


def test_check_leaves_a_snapshot_only_at_its_ceiling(model):
    h = history(3)
    left = []
    res = wgl_tpu.check(model, h, capacity=CAPACITY, max_capacity=THRESHOLD,
                        chunk=CHUNK, snapshot=left)
    assert res["capacity-exceeded"] is True
    snap, = left
    assert snap.cursor % CHUNK == 0 and snap.peak > THRESHOLD
    assert snap.carry[0].shape[0] == THRESHOLD
    left = []
    res = wgl_tpu.check(model, h, capacity=CAPACITY, max_capacity=4096,
                        chunk=CHUNK, snapshot=left)
    assert res["valid"] is True and left == []


def test_adopt_lays_one_devices_rows_over_the_shards(model):
    """Every live row of the snapshot, once; the per-slot arrays and the
    scalars replicated; ``capacity`` rows a shard."""
    left = []
    wgl_tpu.check(model, history(3), capacity=CAPACITY,
                  max_capacity=THRESHOLD, chunk=CHUNK, snapshot=left)
    carry = left[0].carry
    place = sharded.OnMesh(make_mesh((1, 4), devices=jax.devices()[:4]))
    laid = place.adopt(carry, THRESHOLD)

    def live(c):
        v = np.asarray(c[2])
        return sorted(map(tuple, np.concatenate(
            [np.asarray(c[0]), np.asarray(c[1])], axis=1)[v].tolist()))

    assert live(laid) == live(carry) and len(live(carry)) > 0
    assert laid[2].shape == (4 * THRESHOLD,)
    assert laid[2].sharding.spec == jax.sharding.PartitionSpec("model")
    for i in range(3, 17):
        assert np.array_equal(np.asarray(laid[i]), np.asarray(carry[i])), i
        assert laid[i].sharding.is_fully_replicated


def test_the_gather_is_a_named_scope_of_the_mesh_program_only(model):
    mesh = make_mesh((1, 2), devices=jax.devices()[:2])
    place = sharded.OnMesh(mesh)
    carry0, _ = place.runner(model, 12, 16, 1, 8)
    low = sharded.mesh_program(model, 12, 16, 1, 8, mesh, "model", 16).lower(
        carry0(), np.int32(0), place.stage(np.zeros((16, 10), np.int32)))
    assert "wgl.gather_shards" in wgl_tpu.ENGINE_SCOPES
    assert "wgl.gather_shards" in low.as_text(debug_info=True)
    assert "all_gather" in low.as_text() and "wgl." not in low.as_text()
    one0, _, run_chunk = wgl_tpu.make_engine(model, 12, 16, gwords=1)
    text = jax.jit(run_chunk).lower(
        one0(), jnp.zeros((8, 10), jnp.int32)).as_text(debug_info=True)
    assert "wgl.gather_shards" not in text and "all_gather" not in text


# -- the cell's files ----------------------------------------------------------

def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def test_the_cell_loads_through_the_manifest():
    cell = Cell(CELL, manifest())
    assert cell.chips == 4
    assert cell.config["name"] == "multireg-10k-10thread"
    assert cell.config["concurrency"] == 10
    assert cell.config["device_analyzers"] == ["wgl-tpu-sharded"]
    assert cell.traffic["name"] == "offline-multireg-10thread"
    assert {m["name"] for m in cell.end_to_end()} == {"verdict_s", "setup_s"}
    assert plugin("harness.loops", cell.traffic["loop"], "run") \
        is offline_requires.run
    assert callable(plugin("reference", cell.config["reference"], "check"))


def test_the_configuration_is_multireg_10k_but_for_the_threads():
    new, old = load("configs", "multireg-10k-10thread"), \
        load("configs", "multireg-10k")
    same = ("ops", "register_keys", "values", "subsets", "read_p", "write_p",
            "model", "reference", "architecture", "reduced")
    assert {k: new[k] for k in same} == {k: old[k] for k in same}
    assert (old["concurrency"], new["concurrency"]) == (8, 10)
    for k in ("consistency", "refutation", "degraded"):
        assert new["guarantees"][k] == old["guarantees"][k]
    assert "sharded" in new["guarantees"]["path"]
    assert load("traffic", "offline-multireg-10thread")["params"] == \
        load("traffic", "offline-multireg")["params"]


@pytest.mark.parametrize("name,reader,better", [
    ("drivers.shard_share", "span_share", "higher"),
    ("kernels.all_gather_share", "trace_op_share", "lower"),
    ("drivers.shard_balance", "program_stats", "higher")])
def test_the_new_layer_files_load(name, reader, better):
    cell = Cell(CELL, manifest())
    m, = [m for m in cell.per_layer() if m["name"] == name]
    assert m["reader"] == reader and m["better"] == better
    assert (m["moves"], m["unit"], m["workloads"]) == (
        "verdict_s", "%", [CELL])
    assert callable(plugin("readers", reader, "read"))


def test_shard_balance_reads_the_programs_counter(monkeypatch):
    read = plugin("readers", "program_stats", "read")
    args = load("layers", "drivers.shard_balance")["args"]
    sharded.reset_sharded_stats()
    assert read({}, **args) is None         # nothing sharded yet
    monkeypatch.setattr(sharded, "sharded_stats", lambda: {
        "events_sharded": 9, "events_total": 12, "rows_live_min": 30,
        "rows_live_max": 120})
    assert read({}, **args) == 25.0
    monkeypatch.delattr(sharded, "sharded_stats")
    assert read({}, **args) is None         # a program from before it


def test_shard_share_times_the_programs_sharded_entry():
    """The traffic file's span names what ``fission._shard`` calls, by the
    module it looks it up in at each call."""
    target = load("traffic", "offline-multireg-10thread")["spans"]
    assert target == {
        "check_sharded": "jepsen_tpu.parallel.sharded:check_sharded"}
    assert load("layers", "drivers.shard_share")["args"]["span"] in target
    assert sharded.check_sharded.__module__ == "jepsen_tpu.parallel.sharded"


def test_the_generator_gives_ten_processes():
    config = dict(load("configs", "multireg-10k-10thread"), ops=300)
    params = load("traffic", "offline-multireg-10thread")["params"]
    gen = M.multi_register(config, params, seed=2**31 + 5)
    assert gen["keyed"] is False
    assert len({r.process for r in gen["records"]}) == 10
    keys = {k for r in gen["records"] for k, _ in r.value or ()}
    assert keys == {0, 1, 2}


def test_the_loop_requires_what_the_traffic_file_names():
    """A program with the sharded step runs the cell; one without it exits
    non-zero at once, before anything is generated."""
    names = load("traffic", "offline-multireg-10thread")["requires"]
    offline_requires.require(names)
    assert names == ["jepsen_tpu.engine.fission:attached_chips"]
    for missing in ("jepsen_tpu.engine.fission:no_such_step",
                    "jepsen_tpu.no_such_module:anything"):
        with pytest.raises(SystemExit) as e:
            offline_requires.require([missing])
        assert e.value.code not in (0, None) and missing in str(e.value.code)
