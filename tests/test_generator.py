"""Generator DSL semantics via the deterministic simulator
(mirrors the reference's generator test approach: fixed seed, no threads)."""

import pytest

from jepsen_tpu import generator as gen
from jepsen_tpu.generator import testkit
from jepsen_tpu.history import FAIL, INFO, INVOKE, NEMESIS, OK, Op


def invokes(h):
    return [o for o in h if o.type == INVOKE]


class TestLifting:
    def test_dict_is_one_shot(self):
        h = testkit.quick({"f": "read"})
        assert len(invokes(h)) == 1
        assert invokes(h)[0].f == "read"

    def test_list_concats(self):
        h = testkit.quick([{"f": "a"}, {"f": "b"}, {"f": "c"}])
        assert [o.f for o in invokes(h)] == ["a", "b", "c"]

    def test_fn_is_infinite_stream(self):
        counter = {"n": 0}

        def f():
            counter["n"] += 1
            return {"f": "w", "value": counter["n"]}

        h = testkit.quick(gen.limit(5, f))
        assert [o.value for o in invokes(h)] == [1, 2, 3, 4, 5]

    def test_fn_exhausts_on_none(self):
        state = {"n": 0}

        def f():
            state["n"] += 1
            return {"f": "x"} if state["n"] <= 3 else None

        h = testkit.quick(f)
        assert len(invokes(h)) == 3


class TestCombinators:
    def test_limit_and_once(self):
        h = testkit.quick(gen.once(lambda: {"f": "r"}))
        assert len(invokes(h)) == 1

    def test_repeat(self):
        h = testkit.quick(gen.repeat({"f": "r"}, n=4))
        assert [o.f for o in invokes(h)] == ["r"] * 4

    def test_cycle(self):
        h = testkit.quick(gen.cycle([{"f": "a"}, {"f": "b"}], n=3))
        assert [o.f for o in invokes(h)] == ["a", "b"] * 3

    def test_mix_draws_from_all(self):
        r = {"f": "read"}
        w = {"f": "write"}
        h = testkit.quick(gen.limit(50, gen.mix([gen.repeat(r), gen.repeat(w)])))
        fs = {o.f for o in invokes(h)}
        assert fs == {"read", "write"}
        assert len(invokes(h)) == 50

    def test_map_transforms(self):
        h = testkit.quick(gen.gen_map(lambda op: op.with_(value=42),
                                      {"f": "r"}))
        assert invokes(h)[0].value == 42

    def test_f_map(self):
        h = testkit.quick(gen.f_map({"start": "start-partition"},
                                    {"f": "start"}))
        assert invokes(h)[0].f == "start-partition"

    def test_filter(self):
        seq = [{"f": "a", "value": i} for i in range(10)]
        h = testkit.quick(gen.gen_filter(lambda op: op.value % 2 == 0, seq))
        assert [o.value for o in invokes(h)] == [0, 2, 4, 6, 8]

    def test_stagger_spaces_ops(self):
        h = testkit.quick(gen.stagger(0.1, gen.limit(20, lambda: {"f": "r"})),
                          concurrency=1)
        times = [o.time for o in invokes(h)]
        assert times == sorted(times)
        # mean gap should be ~100ms; loose bounds
        gaps = [b - a for a, b in zip(times, times[1:])]
        mean = sum(gaps) / len(gaps)
        assert 20e6 < mean < 400e6

    def test_delay_exact_spacing(self):
        h = testkit.quick(gen.delay(0.05, gen.limit(5, lambda: {"f": "r"})),
                          concurrency=1)
        times = [o.time for o in invokes(h)]
        gaps = {b - a for a, b in zip(times, times[1:])}
        assert gaps == {50_000_000}

    def test_time_limit(self):
        h = testkit.quick(
            gen.time_limit(1.0, gen.delay(0.3, gen.repeat(lambda: {"f": "r"}))),
            concurrency=1)
        assert 2 <= len(invokes(h)) <= 4
        assert all(o.time < 1.1e9 for o in invokes(h))

    def test_process_limit(self):
        h = testkit.quick(gen.process_limit(2, gen.repeat({"f": "r"}, n=50)),
                          concurrency=2)
        assert len({o.process for o in invokes(h)}) <= 2

    def test_flip_flop(self):
        h = testkit.quick(gen.limit(6, gen.flip_flop(
            gen.repeat({"f": "a"}), gen.repeat({"f": "b"}))))
        assert [o.f for o in invokes(h)] == ["a", "b", "a", "b", "a", "b"]

    def test_any_picks_soonest(self):
        a = [gen.sleep(0.5), {"f": "slow"}]
        b = [gen.sleep(0.1), {"f": "fast"}]
        h = testkit.quick(gen.any_gen(a, b), concurrency=4)
        fs = [o.f for o in invokes(h)]
        assert fs[0] == "fast"
        assert set(fs) == {"slow", "fast"}

    def test_each_thread_exhausts_on_immediately_empty_copies(self):
        # Regression: a per-thread copy that dies on its FIRST draw was
        # never recorded as exhausted, so each_thread of an empty
        # generator pended forever (hanging any final-generator phase
        # whose targets were already met).
        h = testkit.simulate({"concurrency": 4},
                             gen.each_thread(gen.limit(0,
                                                       gen.repeat(
                                                           {"f": "x"}))))
        assert len(h) == 0
        # mixed: copies with one op each still all run (clients only)
        h2 = testkit.simulate({"concurrency": 4},
                              gen.clients(gen.each_thread(
                                  gen.limit(1, gen.repeat({"f": "y"})))))
        assert len([o for o in h2 if o.type == INVOKE]) == 4

    def test_any_preserves_sleep_deadline_under_busy_sibling(self):
        # Regression: Any used to discard a pending child's continuation
        # whenever another child produced an op, re-anchoring a Sleep's
        # deadline on every dispense — a `sleep; fault` nemesis schedule
        # racing a busy client stream then fired seconds late (or never).
        busy = gen.stagger(0.001, gen.limit(400, gen.repeat({"f": "c"})))
        delayed = [gen.sleep(0.05), gen.once(gen.lift({"f": "fault"}))]
        h = testkit.quick(gen.any_gen(busy, delayed), concurrency=4)
        fault_t = next(o.time for o in invokes(h) if o.f == "fault")
        # must fire right at its deadline, not after the busy stream ends
        assert 0.05e9 <= fault_t < 0.2e9, fault_t

    def test_sleep_then(self):
        h = testkit.quick([gen.sleep(0.5), {"f": "late"}], concurrency=1)
        op = invokes(h)[0]
        assert op.time >= 0.5e9


class TestThreads:
    def test_clients_vs_nemesis_routing(self):
        g = [gen.nemesis(gen.limit(2, lambda: {"f": "kill", "type": "info"})),
             gen.clients(gen.limit(3, lambda: {"f": "read"}))]
        h = testkit.quick(g, concurrency=3)
        kills = [o for o in h if o.f == "kill" and o.type == "info"]
        reads = invokes(h)
        assert all(o.process == NEMESIS for o in kills)
        assert all(o.process != NEMESIS for o in reads)
        assert len(kills) == 2 and len(reads) == 3

    def test_each_thread(self):
        h = testkit.quick(gen.each_thread({"f": "hi"}), concurrency=3)
        procs = sorted(o.process for o in invokes(h) if o.process != NEMESIS)
        # nemesis thread also runs a copy
        assert procs == [0, 1, 2]
        assert len(invokes(h)) == 4

    def test_reserve_partitions_threads(self):
        g = gen.reserve(2, gen.repeat({"f": "a"}, n=10),
                        gen.repeat({"f": "b"}, n=10))
        h = testkit.quick(gen.time_limit(2.0, g), concurrency=5)
        a_procs = {o.process for o in invokes(h) if o.f == "a"}
        b_procs = {o.process for o in invokes(h) if o.f == "b"}
        assert a_procs <= {0, 1}
        assert b_procs <= {2, 3, 4, NEMESIS}
        assert a_procs and b_procs

    def test_phases_synchronize(self):
        g = gen.phases(gen.limit(4, lambda: {"f": "p1"}),
                       gen.limit(4, lambda: {"f": "p2"}))
        h = testkit.quick(g, concurrency=2)
        last_p1 = max(o.time for o in h if o.f == "p1" and o.type == OK)
        first_p2 = min(o.time for o in h if o.f == "p2" and o.type == INVOKE)
        assert first_p2 >= last_p1

    def test_until_ok_retries_failures(self):
        attempts = {"n": 0}

        def complete(op):
            attempts["n"] += 1
            return (1_000_000, FAIL if attempts["n"] < 3 else OK)

        h = testkit.quick(gen.until_ok(gen.repeat({"f": "w"})),
                          complete_fn=complete, concurrency=1)
        assert [o.type for o in h if o.type in (OK, FAIL)] == [FAIL, FAIL, OK]

    def test_crashed_process_migrates(self):
        def complete(op):
            return (1_000_000, INFO)

        h = testkit.quick(gen.limit(3, gen.repeat(lambda: {"f": "w"})),
                          complete_fn=complete, concurrency=1)
        procs = [o.process for o in invokes(h)]
        # each crash burns a process id: 0, 1, 2 (thread count 1)
        assert procs == [0, 1, 2]


class TestValidate:
    def test_rejects_bad_ops(self):
        with pytest.raises(ValueError):
            testkit.quick(lambda: {"value": 1})  # no :f

    def test_accepts_good(self):
        h = testkit.quick({"f": "ok"})
        assert len(invokes(h)) == 1


class TestPerf:
    def test_scheduler_throughput(self):
        """The reference cites >20k ops/s for pure generator scheduling
        (generator.clj:67-70).  The last idle-host run measured 27.3k
        pure-mix / 21.9k wrapped-stack ops/s, best of 3 (no ledger row:
        the generator is not on the benchmark's path) — this test's bar
        sits WELL below it purely for load tolerance (the suite runs alongside TPU
        benches and real-daemon tests; a 3x slowdown under contention
        has been observed)."""
        import time
        best = 0.0
        for _ in range(3):
            g = gen.limit(20_000, gen.mix([gen.repeat({"f": "r"}),
                                           gen.repeat({"f": "w",
                                                       "value": 1})]))
            t0 = time.time()
            h = testkit.quick(g, concurrency=10,
                              complete_fn=testkit.instant)
            dt = time.time() - t0
            n = len([o for o in h if o.type == INVOKE])
            assert n == 20_000
            best = max(best, n / dt)
        assert best > 8_000, f"scheduler too slow: {best:.0f} ops/s"


class TestConcurrentGeneratorRotation:
    """Regression: with fewer thread groups than keys, a key finishing
    via a final (op, None) draw (limit's exhaustion shape) must free its
    group for the next key — this once parked the group forever and the
    interpreter span on PENDING without terminating."""

    def test_groups_rotate_through_all_keys(self):
        from jepsen_tpu import generator as gen
        from jepsen_tpu import independent
        from jepsen_tpu.generator import testkit

        g = independent.concurrent_generator(
            2, [0, 1, 2, 3, 4],
            lambda k: gen.limit(6, gen.repeat({"f": "write", "value": k})))
        hist = testkit.simulate({"nodes": ["n1"], "concurrency": 4}, g)
        keys = {op.value[0] for op in hist if op.f == "write"}
        assert keys == {0, 1, 2, 3, 4}
        invokes = [op for op in hist if op.type == "invoke"]
        assert len(invokes) == 5 * 6

    def test_groups_progress_concurrently_under_global_stagger(self):
        # Regression: the first group's available op used to win every
        # draw, so an OUTER stagger (which keeps group 0's threads free at
        # each dispense) starved every other group — with one key-group
        # per node, whole nodes had no clients.  The soonest-op rule must
        # let all groups progress interleaved.
        from jepsen_tpu import generator as gen
        from jepsen_tpu import independent
        from jepsen_tpu.generator import testkit

        g = independent.concurrent_generator(
            2, [0, 1, 2],
            lambda k: gen.limit(50, gen.repeat({"f": "write", "value": k})))
        hist = testkit.simulate({"nodes": ["n1"], "concurrency": 6},
                                gen.stagger(0.005, g))
        invs = [op for op in hist if op.type == "invoke"]
        first_40 = {op.value[0] for op in invs[:40]}
        assert first_40 == {0, 1, 2}, first_40  # interleaved, not serial
        threads = {op.process % 6 for op in invs}
        assert threads == {0, 1, 2, 3, 4, 5}, threads


class TestFairness:
    """Scheduling fairness (the reference leans on bifurcan's fair set,
    generator.clj:437-451): free-thread choice must not starve threads or
    generators."""

    def test_threads_share_ops_roughly_equally(self):
        h = testkit.simulate({"concurrency": 4},
                             gen.limit(400, gen.FnGen(
                                 lambda: {"f": "w"})))
        by_p = {}
        for o in invokes(h):
            by_p[o.process] = by_p.get(o.process, 0) + 1
        assert len(by_p) == 4
        lo, hi = min(by_p.values()), max(by_p.values())
        assert lo >= 50, by_p   # no starving under the fixed seed
        assert hi - lo <= 60, by_p

    def test_mix_distribution_is_roughly_uniform(self):
        g = gen.mix([gen.repeat({"f": "a"}), gen.repeat({"f": "b"}),
                     gen.repeat({"f": "c"})])
        h = testkit.quick(gen.limit(600, g))
        counts = {}
        for o in invokes(h):
            counts[o.f] = counts.get(o.f, 0) + 1
        assert set(counts) == {"a", "b", "c"}
        assert all(120 <= c <= 320 for c in counts.values()), counts

    def test_reserve_keeps_ranges_busy_independently(self):
        # one range's generator exhausting must not idle the other range
        g = gen.reserve(2, gen.limit(10, gen.repeat({"f": "a"})),
                        gen.limit(200, gen.repeat({"f": "b"})))
        h = testkit.simulate({"concurrency": 5}, g)
        counts = {}
        for o in invokes(h):
            counts[o.f] = counts.get(o.f, 0) + 1
        assert counts == {"a": 10, "b": 200}, counts


class TestPendingBackoff:
    """:pending semantics: the scheduler waits (bounded poll tick) instead
    of spinning or giving up (interpreter.clj:267 1 ms backoff)."""

    def test_stagger_produces_pending_then_op(self):
        # stagger makes ops due in the future; with no completions pending
        # the simulator advances its 1 ms poll tick until the op is due
        g = gen.time_limit(0.05, gen.stagger(0.01, gen.repeat({"f": "w"})))
        h = testkit.quick(g, concurrency=2,
                          complete_fn=testkit.instant)
        ts = [o.time for o in invokes(h)]
        assert 3 <= len(ts) <= 7, ts     # ~5 ops in 50 ms at 10 ms stagger
        assert all(b >= a for a, b in zip(ts, ts[1:]))

    def test_concurrency_limit_blocks_not_drops(self):
        g = gen.concurrency_limit(1, gen.limit(20, gen.repeat({"f": "w"})))
        h = testkit.simulate({"concurrency": 4}, g)
        evs = [o for o in h if o.type in (INVOKE, OK)]
        # with limit 1 the invoke/ok events must strictly alternate
        for a, b in zip(evs, evs[1:]):
            assert a.type != b.type, [(o.type, o.process) for o in evs[:8]]
        assert len(invokes(h)) == 20


class TestProcessLimitEdges:
    def test_process_limit_counts_crashed_replacements(self):
        # every op crashes; process-limit must stop after N distinct
        # processes even though concurrency never drops
        crash = lambda op: (1_000_000, INFO)
        g = gen.process_limit(5, gen.repeat({"f": "w"}))
        h = testkit.simulate({"concurrency": 2}, g, complete_fn=crash)
        procs = {o.process for o in invokes(h)}
        assert len(procs) == 5, procs

    def test_each_thread_exhausts_independently(self):
        g = gen.each_thread(gen.limit(3, gen.repeat({"f": "w"})))
        h = testkit.simulate({"concurrency": 3}, g)
        by_p = {}
        for o in invokes(h):
            by_p[o.process] = by_p.get(o.process, 0) + 1
        # every thread INCLUDING the nemesis gets its own copy
        # (generator.clj:1001 each-thread includes the nemesis thread)
        assert by_p == {0: 3, 1: 3, 2: 3, "nemesis": 3}, by_p

    def test_each_thread_follows_process_migration(self):
        # a crashed process's replacement (p + concurrency) continues the
        # SAME thread's copy — it must not get a fresh generator
        crashes = iter([True, False, False, False, False, False])
        def complete(op):
            return (1_000_000, INFO if next(crashes, False) else OK)
        g = gen.each_thread(gen.limit(3, gen.repeat({"f": "w"})))
        h = testkit.simulate({"concurrency": 2}, g, complete_fn=complete)
        client_invokes = [o for o in invokes(h) if o.process != "nemesis"
                          and not (isinstance(o.process, str))]
        assert len(client_invokes) == 6, [
            (o.process, o.type) for o in h]


class TestSynchronizeBarrier:
    def test_synchronize_waits_for_stragglers(self):
        # phase 2 must not start until every phase-1 op completed
        g = [gen.limit(6, gen.repeat({"f": "one"})),
             gen.synchronize(gen.limit(2, gen.repeat({"f": "two"})))]
        h = testkit.simulate({"concurrency": 3}, g)
        last_one_ok = max(o.time for o in h
                          if o.type == OK and o.f == "one")
        first_two = min(o.time for o in invokes(h) if o.f == "two")
        assert first_two >= last_one_ok

    def test_any_with_stagger_interleaves(self):
        # any-stagger regression shape (generator_test.clj:509): both
        # sources make progress
        a = gen.stagger(0.001, gen.limit(20, gen.repeat({"f": "a"})))
        b = gen.stagger(0.001, gen.limit(20, gen.repeat({"f": "b"})))
        h = testkit.quick(gen.any_gen(a, b), concurrency=4)
        fs = {o.f for o in invokes(h)}
        assert fs == {"a", "b"}
        assert len(invokes(h)) == 40
