#!/usr/bin/env python
"""Engine smoke: the three engine-plugin models, fuzzed against their
host oracles on the CPU backend.

For each plugin (``linearizable-queue``, ``linearizable-set``,
``opacity``) over a seed sweep:

  1. a valid synthesized history must verify on the device path AND on
     the host oracle (verdict parity, lane for lane);
  2. every corruption mode (lost/duplicated/reordered dequeues,
     phantom/lost set elements, flipped aborted-txn reads) must refute
     on BOTH paths, and the device refutation must carry a recovered
     CPU witness (final-configs), never a bare ``valid: False``;
  3. an impossibly small capacity budget must degrade the verdict to
     ``unknown`` — never fabricate ``False`` on a valid history.

The record — fuzz counts per plugin — goes to the path given as argv[1]
(default /tmp/engine_smoke.json); CI uploads it as an artifact.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from jepsen_tpu import synth  # noqa: E402
from jepsen_tpu.checker import wgl_cpu, wgl_tpu  # noqa: E402
from jepsen_tpu.checker.core import resolve_checker  # noqa: E402
from jepsen_tpu.engine.opacity import derive_history  # noqa: E402
from jepsen_tpu.models import (  # noqa: E402
    FIFOQueue, SetModel, TxnRegister, get_model,
)

SEEDS = range(5)


def log(msg):
    print(f"[engine-smoke +{time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


def assert_refuted_with_witness(res, what):
    assert res["valid"] is False, (what, res)
    assert "op" in res, (what, "refutation without the lane's flag", res)
    w = res.get("witness")
    assert w and w.get("valid") is False and "final-configs" in w, \
        (what, "refutation without a recovered CPU witness", res)


def fuzz_queue():
    checker = resolve_checker("linearizable-queue")
    checks = 0
    for seed in SEEDS:
        h = synth.queue_history(n_ops=40, concurrency=3, seed=seed)
        dev = checker.check(None, h)
        host = wgl_cpu.check(FIFOQueue(), h)
        assert dev["valid"] is True and host["valid"] is True, (seed, dev)
        checks += 1
        bad = synth.corrupt_queue(h, mode="lost", seed=seed)
        dev = checker.check(None, bad)
        assert wgl_cpu.check(FIFOQueue(), bad)["valid"] is False
        assert_refuted_with_witness(dev, f"queue lost seed={seed}")
        checks += 1
        # order-sensitive corruptions on serial histories: refutation
        # can't be absorbed by concurrency
        h1 = synth.queue_history(n_ops=30, concurrency=1, seed=seed)
        for mode in ("duplicated", "reordered"):
            bad = synth.corrupt_queue(h1, mode=mode, seed=seed)
            dev = checker.check(None, bad)
            assert wgl_cpu.check(FIFOQueue(), bad)["valid"] is False
            assert_refuted_with_witness(dev, f"queue {mode} seed={seed}")
            checks += 1
    return checks


def fuzz_set():
    checker = resolve_checker("linearizable-set")
    checks = 0
    for seed in SEEDS:
        h = synth.set_history(n_ops=40, concurrency=3, seed=seed)
        dev = checker.check(None, h)
        assert dev["valid"] is True, (seed, dev)
        assert wgl_cpu.check(SetModel(), h)["valid"] is True
        checks += 1
        bad = synth.corrupt_set(h, mode="phantom", seed=seed)
        dev = checker.check(None, bad)
        assert wgl_cpu.check(SetModel(), bad)["valid"] is False
        assert_refuted_with_witness(dev, f"set phantom seed={seed}")
        checks += 1
        h1 = synth.set_history(n_ops=40, concurrency=1, seed=seed)
        bad = synth.corrupt_set(h1, mode="lost", seed=seed)
        dev = checker.check(None, bad)
        assert wgl_cpu.check(SetModel(), bad)["valid"] is False
        assert_refuted_with_witness(dev, f"set lost seed={seed}")
        checks += 1
    return checks


def fuzz_opacity():
    checker = resolve_checker("opacity")
    checks = 0
    for seed in SEEDS:
        h = synth.txn_history(n_txns=30, concurrency=3, seed=seed)
        dev = checker.check(None, h)
        host = wgl_cpu.check(TxnRegister(), derive_history(h))
        assert dev["valid"] is True and host["valid"] is True, (seed, dev)
        checks += 1
        ha = synth.txn_history(n_txns=30, concurrency=3, abort_p=0.4,
                               seed=seed)
        bad = synth.corrupt_txn_reads(ha, target="fail", seed=seed)
        dev = checker.check(None, bad)
        host = wgl_cpu.check(TxnRegister(), derive_history(bad))
        assert dev["valid"] is False and host["valid"] is False, \
            (seed, dev)
        checks += 1
    return checks


def budget_degrades_to_unknown():
    h = synth.queue_history(n_ops=60, concurrency=5, crash_p=0.05,
                            seed=99)
    m = get_model("fifo-queue", slots=64)
    res = wgl_tpu.check(m, h, capacity=2, max_capacity=2)
    assert res["valid"] is not False, \
        ("budget exhaustion fabricated a refutation", res)
    return res["valid"]


def main():
    out_path = sys.argv[1] if len(sys.argv) > 1 else "/tmp/engine_smoke.json"
    record = {}
    t0 = time.time()
    log("queue parity fuzz")
    record["queue_checks"] = fuzz_queue()
    log("set parity fuzz")
    record["set_checks"] = fuzz_set()
    log("opacity parity fuzz")
    record["opacity_checks"] = fuzz_opacity()
    log("budget exhaustion")
    record["budget_exhaustion_verdict"] = budget_degrades_to_unknown()
    record["wall_s"] = round(time.time() - t0, 1)
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
    log(f"OK: {record['queue_checks'] + record['set_checks'] + record['opacity_checks']} "
        f"parity checks, record -> {out_path}")


if __name__ == "__main__":
    main()
