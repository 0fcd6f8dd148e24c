"""Real-process end-to-end: the localkv suite against actual OS daemons.

Unlike every other pipeline test (fakes/mocks in-process), these spawn real
server processes over the local-exec remote, talk to them over real TCP,
and judge the wire histories with the device checker: safe mode must
verify, follower-local-reads mode must be refuted with per-key artifacts.
"""

import glob
import os

from jepsen_tpu import core

from suites.localkv.runner import localkv_test


def run_localkv(tmp_path, **opts):
    t = localkv_test({
        "nodes": ["n1", "n2", "n3"],
        "concurrency": 6,
        "time_limit": 4.0,
        "keys": 2,
        "store_base": str(tmp_path / "store"),
        "localkv_dir": str(tmp_path / "localkv"),
        **opts,
    })
    return core.run(t)


class TestLocalKv:
    def test_safe_mode_verifies(self, tmp_path):
        done = run_localkv(tmp_path, nemesis="none")
        assert done["results"]["valid"] is True
        # the history came from real daemons: their WALs were snarfed
        wals = glob.glob(os.path.join(done["store_dir"], "n*", "wal.jsonl"))
        assert wals and any(os.path.getsize(w) > 0 for w in wals)

    def test_kill_nemesis_recovers(self, tmp_path):
        done = run_localkv(tmp_path, nemesis="kill", nemesis_interval=1.0,
                           time_limit=8.0)
        # real SIGKILLs: correctness must survive them (INFO ops allowed)
        assert done["results"]["valid"] is True, \
            list(core.iter_analysis_errors(done["results"]))
        fs = [op.f for op in done["history"]
              if getattr(op, "process", None) == "nemesis"]
        assert "kill" in fs and "start" in fs

    def test_unsafe_mode_refuted_with_artifacts(self, tmp_path):
        done = run_localkv(tmp_path, unsafe=True, nemesis="none")
        assert done["results"]["valid"] is False
        bad = done["results"]["workload"]["failures"]
        assert bad
        svg = os.path.join(done["store_dir"], "independent", str(bad[0]),
                           "linear.svg")
        assert os.path.exists(svg)
        # a refuted key keeps the batch's leaf; the host confirms: witness
        r = done["results"]["workload"]["results"][bad[0]]
        assert r["valid"] is False and "witness" in r

    def test_partition_nemesis_safe_mode_verifies(self, tmp_path):
        """Real sockets severed mid-run by the proxy-net partitioner: safe
        mode (all ops through the primary) must stay linearizable — the
        partitioned follower's ops fail/hang, they don't corrupt."""
        done = run_localkv(tmp_path, nemesis="partition",
                           nemesis_interval=1.5, time_limit=8.0)
        assert done["results"]["valid"] is True, \
            list(core.iter_analysis_errors(done["results"]))
        fs = [op.f for op in done["history"]
              if getattr(op, "process", None) == "nemesis"]
        assert "start-partition" in fs and "stop-partition" in fs
        # the partition really bit: some ops failed or went indeterminate
        # while the grudge held
        ntypes = [op.type for op in done["history"]
                  if getattr(op, "process", None) != "nemesis"]
        assert "fail" in ntypes or "info" in ntypes

    def test_partition_with_local_reads_refuted(self, tmp_path):
        """Severing replication to a follower that serves local reads must
        produce a real, machine-checked linearizability violation.  The
        hold schedule severs one follower from t=1s until the final heal —
        a forced multi-second staleness window, not a lucky start/stop
        cycle (the cycling variant flaked under full-suite load)."""
        # keys=3: all 6 workers active (2 per node), so whichever follower
        # the grudge severs has pinned readers (keys=2 left a node with no
        # clients and the refutation hinged on the grudge's coin flip).
        done = run_localkv(tmp_path, unsafe=True, nemesis="partition-hold",
                           nemesis_delay=1.0, time_limit=8.0, keys=3,
                           repl_delay=0.0, unique_writes=True,
                           ops_per_key=1000, stagger_s=0.02)
        assert done["results"]["valid"] is False
        assert done["results"]["workload"]["failures"]
