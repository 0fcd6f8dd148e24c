"""chip_smoke.py's control flow on the CPU backend.

The script itself only runs on a TPU; here ``main()`` must refuse the CPU
at once, and each phase function, called directly at a toy size, must
reach host-oracle parity (the phases raise on any mismatch).  No timing
is asserted: a wall from this backend says nothing about the chip.
"""

import pytest

import chip_smoke


def test_main_refuses_cpu_before_any_work(monkeypatch):
    for name in ("phase_offline", "phase_hard", "phase_keyed",
                 "phase_served"):
        monkeypatch.setattr(
            chip_smoke, name,
            lambda *a, **kw: pytest.fail("a phase ran without a TPU"))
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert "no TPU" in str(e.value.code) and "'cpu'" in str(e.value.code)


def test_last_stdout_line_is_the_stamp_alone(monkeypatch, capsys):
    import json
    stamp = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "phase_device",
                        lambda: {"device": dict(stamp), "versions": {}})
    for name in ("phase_offline", "phase_hard", "phase_keyed",
                 "phase_served"):
        monkeypatch.setattr(chip_smoke, name, lambda seed: {})
    monkeypatch.setattr(chip_smoke, "phase_report",
                        lambda watch: {"compile": {}, "cache": {}})
    assert chip_smoke.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": stamp}
    report = json.loads(lines[-2])["report"]
    assert set(report["phases"]) == {"offline", "hard", "keyed", "served"}


def test_report_reads_the_programs_own_compile_counters(monkeypatch):
    """``phase_report`` takes JAX's compile and cache events from
    ``first_use_stats()`` between two marks (no listener of its own), under
    the keys its ``{"report": ...}`` line always had."""
    import jax
    import jax.numpy as jnp

    from jepsen_tpu.obs import hist
    hist.listen_first_use()
    monkeypatch.setattr(chip_smoke, "require", lambda *a: None)  # no cache
    watch = hist.first_use_stats()

    @jax.jit
    def smoke_op(x):
        return x + 11
    smoke_op(jnp.ones(2))
    report = chip_smoke.phase_report(watch)
    assert set(report["compile"]) == {
        "engine_first_calls", "engine_first_call_s", "engines_s",
        "backend_compiles", "backend_compile_s", "persistent_cache_hits",
        "persistent_cache_misses"}
    assert report["compile"]["backend_compiles"] == \
        hist.first_use_stats()["programs"] - watch["programs"] >= 1
    assert report["compile"]["persistent_cache_hits"] == 0
    assert set(report["cache"]) == {"dir", "entries", "from_env"}
    assert not hasattr(chip_smoke, "CompileWatch")


def test_phase_offline_toy():
    obs = chip_smoke.phase_offline(0, n_ops=120)
    assert obs["ops"] == 120 and obs["configs_explored"] > 0


def test_phase_hard_toy():
    obs = chip_smoke.phase_hard(0, n_ops=80, n_doomed=4)
    assert obs["window"] >= 4


def test_phase_keyed_toy():
    obs = chip_smoke.phase_keyed(0, n_keys=8, n_ops=30)
    # every fourth lane carries a corrupted read
    assert obs["keys"] == 8 and obs["refuted"] == 2


def test_phase_served_toy():
    obs = chip_smoke.phase_served(0, n_small=8, small_ops=30, big_ops=120,
                                  n_elle=3, elle_txns=20)
    assert obs["requests"] == 12
    assert obs["megabatch_dispatches"] > 0 and obs["host_fallbacks"] == 0
    assert obs["elle_refuted"] >= 1


def test_host_answers_are_rejected():
    ok = {"valid": True, "analyzer": "wgl-tpu"}
    chip_smoke.require_device_answer(ok, "ok")
    for bad in ({"valid": "unknown", "analyzer": "wgl-tpu"},
                {**ok, "fallback-chain": [{"solver": "wgl-tpu"}]},
                {**ok, "solver": "wgl-cpu"},
                {"valid": True, "results": {0: {"valid": True,
                                                "analyzer": "elle-cpu"}}}):
        with pytest.raises(RuntimeError):
            chip_smoke.require_device_answer(bad, "bad")
    # the CPU witness re-derivation is host work by design
    chip_smoke.require_device_answer(
        {"valid": False, "analyzer": "wgl-tpu",
         "witness": {"valid": False, "analyzer": "wgl-cpu"}}, "witnessed")
