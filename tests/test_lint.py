"""The static analyzer's own test suite.

Three layers: per-rule positive/negative fixtures (each injected defect
produces exactly the expected finding, each legal idiom produces none),
the suppression machinery (pragmas and the committed baseline), and the
jaxpr trace tier (banned primitives, plus a deliberately shape-leaking
fixture engine the ladder check must catch).  The guard tests at the
bottom pin the analyzer to exit clean on the repo itself — the PR
contract is fixed findings, not baselined ones.
"""

import ast
import json
import re
import textwrap
import time

import pytest

from jepsen_tpu.lint.ast_lint import run_ast_tier
from jepsen_tpu.lint.findings import (Baseline, Finding, apply_pragmas,
                                      pragma_rules, to_sarif)
from jepsen_tpu.lint.interp_lint import run_interp_tier
from jepsen_tpu.lint.rules import (atom01, conc01, conc02, dev01, dl01,
                                   env01, obs01, race01, res01, sec01,
                                   shape01, sound01)


def run_rule(rule, src, path):
    src = textwrap.dedent(src)
    return list(rule.check(ast.parse(src), src.splitlines(), path))


# ---------------------------------------------------------------------------
# SOUND01
# ---------------------------------------------------------------------------

class TestSound01:
    PATH = "jepsen_tpu/checker/fixture.py"

    def test_fallback_in_except_flagged(self):
        fs = run_rule(sound01, """
            def check(h):
                try:
                    return engine(h)
                except Exception:
                    return {"valid": False, "analyzer": "x"}
            """, self.PATH)
        assert len(fs) == 1
        assert fs[0].rule == "SOUND01"
        assert "except handler" in fs[0].message

    def test_unwitnessed_literal_flagged(self):
        fs = run_rule(sound01, """
            def check(h):
                return {"valid": False, "analyzer": "x"}
            """, self.PATH)
        assert len(fs) == 1
        assert "witness-bearing" in fs[0].message

    def test_subscript_store_flagged(self):
        fs = run_rule(sound01, """
            def check(h, r):
                try:
                    pass
                except ValueError:
                    r["valid"] = False
                return r
            """, self.PATH)
        assert len(fs) == 1
        assert "except handler" in fs[0].message

    def test_witness_annotation_accepted(self):
        fs = run_rule(sound01, """
            def check(h):
                # witness: refuting op attached
                return {"valid": False, "op": h[0]}
            """, self.PATH)
        assert fs == []

    def test_whitelist_accepted(self):
        fs = run_rule(sound01, """
            def check(model, history):
                return {"valid": False, "op": history[0]}
            """, "jepsen_tpu/checker/wgl_cpu.py")
        assert fs == []

    def test_unknown_degrade_is_legal(self):
        fs = run_rule(sound01, """
            def check(h):
                try:
                    return engine(h)
                except Exception as e:
                    return {"valid": "unknown", "error": str(e)}
            """, self.PATH)
        assert fs == []

    def test_computed_verdict_out_of_scope(self):
        fs = run_rule(sound01, """
            def check(h):
                errors = scan(h)
                return {"valid": not errors, "errors": errors}
            """, self.PATH)
        assert fs == []


# ---------------------------------------------------------------------------
# DEV01
# ---------------------------------------------------------------------------

class TestDev01:
    PATH = "jepsen_tpu/parallel/fixture.py"

    def test_item_in_jitted_engine_flagged(self):
        fs = run_rule(dev01, """
            import jax

            def make(w):
                def run_chunk(carry, events):
                    return carry, events.sum().item()
                return jax.jit(run_chunk)
            """, self.PATH)
        assert len(fs) == 1
        assert ".item()" in fs[0].message
        assert "run_chunk" in fs[0].message

    def test_data_dependent_branch_flagged(self):
        fs = run_rule(dev01, """
            import jax

            def make(w):
                def run_chunk(carry, events):
                    x = events.sum()
                    if x > 0:
                        carry = carry + 1
                    return carry
                return jax.jit(run_chunk)
            """, self.PATH)
        assert len(fs) == 1
        assert "data-dependent" in fs[0].message

    def test_numpy_and_concretize_on_tracer_flagged(self):
        fs = run_rule(dev01, """
            import jax
            import numpy as np

            def make(w):
                def run_chunk(carry, events):
                    z = np.asarray(events)
                    n = int(events.sum())
                    return carry, z, n
                return jax.jit(run_chunk)
            """, self.PATH)
        rules = sorted(f.message.split(" ")[0] for f in fs)
        assert len(fs) == 2
        assert any("np.asarray" in f.message for f in fs)
        assert any("`int()`" in f.message for f in fs)

    def test_static_closure_branch_is_legal(self):
        fs = run_rule(dev01, """
            import jax

            def make(w, single_round):
                def run_chunk(carry, events):
                    n = events.shape[0]
                    if single_round:
                        carry = carry + n
                    if w > 8:
                        carry = carry * 2
                    return carry
                return jax.jit(run_chunk)
            """, self.PATH)
        assert fs == []

    def test_shape_len_isnone_untaint(self):
        fs = run_rule(dev01, """
            import jax

            def make(enable):
                def run_chunk(carry, events):
                    if events.ndim == 2:
                        carry = carry + 1
                    if len(events.shape) == 2:
                        carry = carry + 1
                    if enable is not None:
                        carry = carry + 1
                    return carry
                return jax.jit(run_chunk)
            """, self.PATH)
        assert fs == []

    def test_called_helper_is_traced_too(self):
        fs = run_rule(dev01, """
            import jax

            def helper(x):
                return x.sum().item()

            def make(w):
                def run_chunk(carry, events):
                    return carry, helper(events)
                return jax.jit(run_chunk)
            """, self.PATH)
        assert len(fs) == 1
        assert "helper" in fs[0].message

    def test_host_driver_not_traced(self):
        # .item() in the *host* driver (never passed to jit) is fine
        fs = run_rule(dev01, """
            import numpy as np

            def drive(flags):
                return int(np.asarray(flags)[0]), flags.sum().item()
            """, self.PATH)
        assert fs == []


# ---------------------------------------------------------------------------
# SHAPE01
# ---------------------------------------------------------------------------

class TestShape01:
    PATH = "jepsen_tpu/serve/fixture.py"

    def test_raw_shape_floor_flagged(self):
        fs = run_rule(shape01, """
            from jepsen_tpu.parallel.batch import check_batch

            def dispatch(model, hs):
                return check_batch(model, hs, window_floor=max(
                    len(h) for h in hs))
            """, self.PATH)
        assert len(fs) == 1
        assert "not derived from the bucket ladder" in fs[0].message

    def test_missing_floor_flagged(self):
        fs = run_rule(shape01, """
            from jepsen_tpu.elle_tpu.engine import check_batch

            def dispatch(hs):
                return check_batch(hs, workload="list-append")
            """, self.PATH)
        assert len(fs) == 1
        assert "n_pad_floor" in fs[0].message

    def test_nonzero_literal_flagged(self):
        fs = run_rule(shape01, """
            from jepsen_tpu.parallel.batch import check_batch

            def dispatch(model, hs):
                return check_batch(model, hs, window_floor=24)
            """, self.PATH)
        assert len(fs) == 1

    def test_bucket_derived_accepted(self):
        fs = run_rule(shape01, """
            from jepsen_tpu.engine import ladder
            from jepsen_tpu.parallel.batch import check_batch

            def dispatch(model, hs, padded):
                w_bucket = max(ladder.width_bucket(h) for h in hs)
                ev_bucket = max(ladder.events_bucket(h) for h in hs)
                return check_batch(
                    model, padded,
                    chunk=ladder.batch_chunk(len(padded), ev_bucket),
                    window_floor=w_bucket)
            """, self.PATH)
        assert fs == []

    def test_megabatch_missing_floors_flagged(self):
        fs = run_rule(shape01, """
            from jepsen_tpu.parallel.megabatch import check_megabatch

            def dispatch(model, hs):
                return check_megabatch(model, hs, lanes=len(hs))
            """, self.PATH)
        assert len(fs) == 3      # off-ladder lanes + both missing floors
        msgs = "\n".join(f.message for f in fs)
        assert "window_floor" in msgs and "ev_floor" in msgs
        assert "not derived from the bucket ladder" in msgs

    def test_megabatch_ladder_shapes_accepted(self):
        fs = run_rule(shape01, """
            from jepsen_tpu.engine import ladder
            from jepsen_tpu.parallel.megabatch import check_megabatch

            def dispatch(model, hs, ev_bucket, w_bucket):
                return check_megabatch(
                    model, hs, window_floor=w_bucket, ev_floor=ev_bucket,
                    lanes=ladder.mega_lane_bucket(len(hs)))
            """, self.PATH)
        assert fs == []

    def test_cpu_engine_exempt(self):
        fs = run_rule(shape01, """
            from jepsen_tpu.elle_tpu.engine import check_batch

            def host_fallback(h):
                return check_batch([h], engine="cpu")[0]
            """, self.PATH)
        assert fs == []

    def test_out_of_scope_path_ignored(self):
        assert not any("jepsen_tpu/parallel/x.py".startswith(p)
                       for p in shape01.SCOPE)


# ---------------------------------------------------------------------------
# CONC01
# ---------------------------------------------------------------------------

class TestConc01:
    def test_wallclock_deadline_in_serve_flagged(self):
        fs = run_rule(conc01, """
            import time

            def expired(self, deadline):
                return time.time() > deadline
            """, "jepsen_tpu/serve/fixture.py")
        assert len(fs) == 1
        assert "wall clock" in fs[0].message
        assert "mono_now" in fs[0].hint

    def test_wallclock_alias_flagged(self):
        fs = run_rule(conc01, """
            import time as _time

            def f():
                return _time.time()
            """, "jepsen_tpu/db.py")
        assert len(fs) == 1

    def test_monotonic_is_legal(self):
        fs = run_rule(conc01, """
            import time

            def f():
                return time.monotonic()
            """, "jepsen_tpu/serve/fixture.py")
        assert fs == []

    def test_wallclock_lease_bookkeeping_flagged(self):
        # lease arithmetic on the wall clock steps under NTP adjustment
        # and evicts healthy workers (or keeps dead ones) on a time jump
        fs = run_rule(conc01, """
            import time

            def renew(self, rec, lease_s):
                rec.lease_expires_at = time.time() + lease_s
                return rec.lease_expires_at - time.time()
            """, "jepsen_tpu/serve/registry.py")
        assert len(fs) == 2
        assert all("wall clock" in f.message for f in fs)
        assert all("mono_now" in f.hint for f in fs)

    def test_monotonic_lease_bookkeeping_legal(self):
        fs = run_rule(conc01, """
            from jepsen_tpu.clock import mono_now

            def renew(self, rec, lease_s):
                rec.lease_expires_at = mono_now() + lease_s
                return rec.lease_expires_at - mono_now()
            """, "jepsen_tpu/serve/registry.py")
        assert fs == []

    def test_registry_above_slot_lock_legal(self):
        fs = run_rule(conc01, """
            class FleetRegistry:
                def bind(self, worker):
                    with self._lock:
                        with worker._restart_lock:
                            pass
            """, "jepsen_tpu/serve/registry.py")
        assert fs == []

    def test_registry_under_slot_lock_flagged(self):
        fs = run_rule(conc01, """
            class FleetRegistry:
                def bind(self, worker):
                    with worker._restart_lock:
                        with self._lock:
                            pass
            """, "jepsen_tpu/serve/registry.py")
        assert len(fs) == 1
        assert "lock-order inversion" in fs[0].message

    def test_lock_order_inversion_flagged(self):
        fs = run_rule(conc01, """
            class Service:
                def finalize(self, req):
                    with req._lock:
                        with self._lock:
                            pass
            """, "jepsen_tpu/serve/service.py")
        assert len(fs) == 1
        assert "lock-order inversion" in fs[0].message

    def test_manifest_order_is_legal(self):
        fs = run_rule(conc01, """
            class Service:
                def finalize(self, req):
                    with self._lock:
                        with req._lock:
                            pass
            """, "jepsen_tpu/serve/service.py")
        assert fs == []

    def test_blocking_io_under_lock_flagged(self):
        fs = run_rule(conc01, """
            import time

            class Service:
                def f(self):
                    with self._lock:
                        time.sleep(1.0)
            """, "jepsen_tpu/serve/service.py")
        assert len(fs) == 1
        assert "blocking call" in fs[0].message

    def test_nested_def_resets_held_locks(self):
        # the closure body runs later, outside the lock
        fs = run_rule(conc01, """
            import time

            class Service:
                def f(self):
                    with self._lock:
                        def later():
                            time.sleep(1.0)
                        return later
            """, "jepsen_tpu/serve/service.py")
        assert fs == []

    def test_undeclared_locks_not_ordered(self):
        fs = run_rule(conc01, """
            class Proxy:
                def f(self, other):
                    with other._mu:
                        with self._mu:
                            pass
            """, "jepsen_tpu/net_proxy.py")
        assert fs == []


# ---------------------------------------------------------------------------
# OBS01
# ---------------------------------------------------------------------------

class TestObs01:
    PATH = "jepsen_tpu/serve/fixture.py"

    def test_wall_duration_in_record_flagged(self):
        fs = run_rule(obs01, """
            import time

            def flush(self, t0):
                RECORDER.record("monitor", "epoch",
                                dur_s=time.time() - t0)
            """, self.PATH)
        assert len(fs) == 1
        assert fs[0].rule == "OBS01"
        assert "monotonic" in fs[0].message
        assert "mono_now" in fs[0].hint

    def test_wall_anchor_duration_flagged(self):
        fs = run_rule(obs01, """
            def flush(self, span):
                RECORDER.record("serve", "dispatch",
                                t=span.end - self.anchor_unix_s)
            """, self.PATH)
        assert len(fs) >= 1
        assert any("anchor" in f.message or "monotonic" in f.message
                   for f in fs)

    def test_anchor_arithmetic_flagged(self):
        fs = run_rule(obs01, """
            def age(self, span_t0):
                return span_t0 + self.trace.anchor_unix_s
            """, self.PATH)
        assert len(fs) == 1
        assert "anchor" in fs[0].message

    def test_handbuilt_trace_context_flagged(self):
        fs = run_rule(obs01, """
            def absorb(self):
                return {"trace-id": "t-1", "span-id": new_span_id()}
            """, self.PATH)
        assert len(fs) == 1
        assert "trace identity" in fs[0].message

    def test_fstring_trace_id_flagged(self):
        fs = run_rule(obs01, """
            def absorb(self, wid):
                return {"trace-id": f"w{wid}", "parent-span-id": self.sid}
            """, self.PATH)
        assert len(fs) == 1

    def test_monotonic_and_plumbed_ids_clean(self):
        fs = run_rule(obs01, """
            def flush(self, t0):
                wall = mono_now() - t0
                RECORDER.record("monitor", "epoch", dur_s=wall)
                return {"trace-id": self.trace_id,
                        "span-id": new_span_id()}
            """, self.PATH)
        assert fs == []

    def test_non_span_dict_ignored(self):
        # a trace-id alone (no span-id key) is reporting, not a context
        fs = run_rule(obs01, """
            def status(self):
                return {"trace-id": "none", "spans": 0}
            """, self.PATH)
        assert fs == []

    def test_pragma_escape(self):
        src = ("def export(self, t0):\n"
               "    # lint: disable=OBS01(export-only wall anchor)\n"
               "    return t0 + self.anchor_unix_s\n")
        findings, _ = run_ast_tier(
            files={"jepsen_tpu/serve/exporter_fixture.py": src})
        assert findings == []

    def test_out_of_scope_path_ignored(self):
        assert not any("jepsen_tpu/engine/x.py".startswith(p)
                       for p in obs01.SCOPE)


# ---------------------------------------------------------------------------
# pragmas and baseline
# ---------------------------------------------------------------------------

class TestSuppression:
    def test_pragma_parse(self):
        lines = ["x = time.time()  # lint: disable=CONC01(user-facing)"]
        assert pragma_rules(lines, 1) == {"CONC01": "user-facing"}

    def test_pragma_line_above(self):
        lines = ["# lint: disable=SOUND01(oracle), DEV01",
                 "return {'valid': False}"]
        assert pragma_rules(lines, 2) == {"SOUND01": "oracle", "DEV01": ""}

    def test_pragma_suppresses_finding(self):
        f = Finding("CONC01", "jepsen_tpu/x.py", 2, "m")
        sources = {"jepsen_tpu/x.py": [
            "# lint: disable=CONC01(benchmark wall)", "t = time.time()"]}
        assert apply_pragmas([f], sources) == []

    def test_pragma_other_rule_does_not_suppress(self):
        f = Finding("SOUND01", "jepsen_tpu/x.py", 2, "m")
        sources = {"jepsen_tpu/x.py": [
            "# lint: disable=CONC01(benchmark wall)", "bad()"]}
        assert apply_pragmas([f], sources) == [f]

    def test_baseline_roundtrip_and_mark(self, tmp_path):
        p = str(tmp_path / "baseline.json")
        legacy = Finding("CONC01", "jepsen_tpu/a.py", 5, "legacy msg")
        Baseline.write([legacy], p, justification="pre-existing debt")
        data = json.loads(open(p).read())
        assert data["findings"][0]["justification"] == "pre-existing debt"

        bl = Baseline.load(p)
        fresh = Finding("CONC01", "jepsen_tpu/a.py", 9, "new msg")
        moved = Finding("CONC01", "jepsen_tpu/a.py", 50, "legacy msg")
        marked = bl.mark([fresh, moved])
        assert not marked[0].baselined          # new finding still fails
        assert marked[1].baselined              # line drift doesn't churn

    def test_empty_baseline_marks_nothing(self, tmp_path):
        bl = Baseline.load(str(tmp_path / "missing.json"))
        f = Finding("DEV01", "jepsen_tpu/a.py", 1, "m")
        assert bl.mark([f]) == [f] and not f.baselined


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

class TestDriver:
    def test_injected_files_and_parse_error(self):
        findings, sources = run_ast_tier(files={
            "jepsen_tpu/serve/bad.py": "def f(:\n",
            "jepsen_tpu/checker/ok.py": "def f():\n    return 1\n",
        })
        assert [f.rule for f in findings] == ["PARSE"]
        assert "jepsen_tpu/checker/ok.py" in sources

    def test_driver_applies_pragmas(self):
        src = ("import time\n"
               "def f():\n"
               "    # lint: disable=CONC01(user-facing wall clock)\n"
               "    return time.time()\n")
        findings, _ = run_ast_tier(files={"jepsen_tpu/serve/x.py": src})
        assert findings == []


# ---------------------------------------------------------------------------
# jaxpr trace tier
# ---------------------------------------------------------------------------

class TestTraceTier:
    def test_clean_fn_passes(self):
        import jax.numpy as jnp
        from jepsen_tpu.lint.jaxpr_lint import check_jaxpr_clean
        fs = check_jaxpr_clean(lambda x: (x * 2).sum(),
                               (jnp.zeros((4,), jnp.int32),), "clean")
        assert fs == []

    def test_callback_engine_caught(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jepsen_tpu.lint.jaxpr_lint import check_jaxpr_clean

        def leaky(x):
            out = jax.ShapeDtypeStruct(x.shape, x.dtype)
            return jax.pure_callback(lambda a: np.asarray(a), out, x)

        fs = check_jaxpr_clean(leaky, (jnp.zeros((4,), jnp.float32),),
                               "leaky-engine")
        assert len(fs) == 1
        assert "pure_callback" in fs[0].message

    def test_untraceable_engine_is_a_finding(self):
        import jax.numpy as jnp
        from jepsen_tpu.lint.jaxpr_lint import check_jaxpr_clean

        def broken(x):
            if x.sum() > 0:          # concretization error at trace time
                return x
            return -x

        fs = check_jaxpr_clean(broken, (jnp.zeros((4,), jnp.int32),),
                               "broken-engine")
        assert len(fs) == 1
        assert "failed to trace" in fs[0].message

    def test_shape_leaking_fixture_engine_caught(self):
        from jepsen_tpu.lint.jaxpr_lint import signature_stability_findings
        # several raw sizes per bucket: the leak shows as |sigs| > |buckets|
        samples = [(5, 1, 1), (63, 2, 2), (65, 3, 4), (100, 5, 7),
                   (300, 11, 64), (1000, 24, 200)]

        def bucket(s):
            return (max(64, 1 << (s[0] - 1).bit_length()),)

        def leaking_signature(s):
            return (s[0],)           # pads to the raw history length

        fs = signature_stability_findings(samples, leaking_signature,
                                          bucket, "fixture engine")
        assert len(fs) == 1
        assert "raw shape is leaking" in fs[0].message

        fs_ok = signature_stability_findings(samples, bucket, bucket,
                                             "fixture engine")
        assert fs_ok == []

    def test_state_width_leak_fixture_pair(self):
        # the state-width axis through the real derivations: a signature
        # built from the quantized bucket is stable (negative fixture),
        # one threading the RAW model width into chunk/capacity fans a
        # bucket out into many signatures (positive fixture).
        from jepsen_tpu.engine.ladder import (
            mega_chunk, state_capacity, state_width_bucket,
        )
        from jepsen_tpu.lint.jaxpr_lint import signature_stability_findings
        # several raw widths per rung: 5..8 share the 8-rung, 9..16 the 16
        samples = [(64, 8, w) for w in (5, 6, 7, 8, 9, 12, 16, 17, 30)]

        def bucket(s):
            return (s[0], s[1], state_width_bucket(s[2]))

        def good_signature(s):
            # mega_chunk/state_capacity quantize internally — same rung,
            # same compiled shape
            return (mega_chunk(64, s[0], s[2]),
                    state_capacity(s[0], s[1], s[2]))

        assert signature_stability_findings(
            samples, good_signature, bucket, "state-width fixture") == []

        def leaking_signature(s):
            return (mega_chunk(64, s[0], s[2]),
                    s[2])            # raw width reaches the jit boundary

        fs = signature_stability_findings(
            samples, leaking_signature, bucket, "state-width fixture")
        assert len(fs) == 1
        assert "raw shape is leaking" in fs[0].message

    def test_real_ladder_is_stable(self):
        from jepsen_tpu.lint.jaxpr_lint import ladder_findings
        assert ladder_findings() == []

    def test_real_engines_trace_clean(self):
        from jepsen_tpu.lint.jaxpr_lint import trace_engine_findings
        assert trace_engine_findings() == []


# ---------------------------------------------------------------------------
# interprocedural tier: CONC02 / SEC01 / DL01 fixture pairs
# ---------------------------------------------------------------------------

def run_interp(files, rules=None):
    files = {p: textwrap.dedent(s) for p, s in files.items()}
    findings, _ = run_interp_tier(files=files, rules=rules)
    return findings


class TestConc02:
    #: the PR 14 pair: a registry-lock holder calling into a fleet-lock
    #: acquirer — invisible to CONC01 (two functions), caught by CONC02
    INVERSION = {
        "jepsen_tpu/serve/fleet.py": """
            import threading
            class Fleet:
                def __init__(self):
                    self._lock = threading.Lock()
                def poke(self):
                    with self._lock:
                        pass
            """,
        "jepsen_tpu/serve/registry.py": """
            import threading
            from jepsen_tpu.serve.fleet import Fleet
            class Registry:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.fleet = Fleet()
                def bad(self):
                    with self._lock:
                        self.fleet.poke()
            """,
    }

    def test_cross_function_inversion_caught(self):
        fs = [f for f in run_interp(self.INVERSION, rules=[conc02])
              if "inversion" in f.message]
        assert len(fs) == 1
        f = fs[0]
        assert f.rule == "CONC02"
        assert f.path == "jepsen_tpu/serve/registry.py"
        assert "registry.py::Registry.bad -> fleet.py::Fleet.poke" \
            in f.message
        assert "'fleet'" in f.message and "'fleet-registry'" in f.message

    def test_conc01_cannot_see_it(self):
        src = textwrap.dedent(
            self.INVERSION["jepsen_tpu/serve/registry.py"])
        fs = run_rule(conc01, src, "jepsen_tpu/serve/registry.py")
        assert [f for f in fs if "order" in f.message] == []

    def test_manifest_order_negative(self):
        files = {
            "jepsen_tpu/serve/fleet.py": """
                import threading
                from jepsen_tpu.serve.registry import Registry
                class Fleet:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self.reg = Registry()
                    def ok(self):
                        with self._lock:
                            self.reg.bind()
                """,
            "jepsen_tpu/serve/registry.py": """
                import threading
                class Registry:
                    def __init__(self):
                        self._lock = threading.Lock()
                    def bind(self):
                        with self._lock:
                            pass
                """,
        }
        fs = [f for f in run_interp(files, rules=[conc02])
              if "inversion" in f.message]
        assert fs == []

    def test_thread_seam_does_not_propagate(self):
        """Spawning a thread under a lock is not an inversion: the
        target runs on a fresh stack without the spawner's locks."""
        files = dict(self.INVERSION)
        files["jepsen_tpu/serve/registry.py"] = """
            import threading
            from jepsen_tpu.serve.fleet import Fleet
            class Registry:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.fleet = Fleet()
                def spawn(self):
                    with self._lock:
                        threading.Thread(target=self.fleet.poke).start()
            """
        fs = [f for f in run_interp(files, rules=[conc02])
              if "inversion" in f.message]
        assert fs == []

    def test_interprocedural_message_is_line_free(self):
        fs = [f for f in run_interp(self.INVERSION, rules=[conc02])
              if "inversion" in f.message]
        assert not re.search(r"\d+:\d+|line \d+", fs[0].message)

    def test_undeclared_lock_drift_flagged(self):
        files = {"jepsen_tpu/serve/widget.py": """
            import threading
            class Widget:
                def __init__(self):
                    self._zlock = threading.Lock()
            """}
        fs = run_interp(files, rules=[conc02])
        assert len(fs) == 1
        assert "undeclared lock `self._zlock`" in fs[0].message
        assert "Widget.__init__" in fs[0].message

    def test_drift_pragma_suppresses(self):
        files = {"jepsen_tpu/serve/widget.py": """
            import threading
            class Widget:
                def __init__(self):
                    # lint: disable=CONC02(leaf lock, never nested)
                    self._zlock = threading.Lock()
            """}
        assert run_interp(files, rules=[conc02]) == []

    def test_drift_out_of_scope_tree_ignored(self):
        files = {"jepsen_tpu/engine/widget.py": """
            import threading
            class Widget:
                def __init__(self):
                    self._zlock = threading.Lock()
            """}
        assert run_interp(files, rules=[conc02]) == []


class TestSec01:
    AUTH = {
        "jepsen_tpu/serve/auth.py": """
            import os
            TOKEN_ENV = "JEPSEN_TPU_FLEET_TOKEN"
            AUTH_FIELD = "auth"
            def fleet_token():
                return os.environ.get(TOKEN_ENV, "") or None
            """,
    }

    def test_token_through_helper_into_log_caught(self):
        files = dict(self.AUTH)
        files["jepsen_tpu/serve/boot.py"] = """
            import logging
            from jepsen_tpu.serve.auth import fleet_token
            log = logging.getLogger(__name__)
            def _banner(tok):
                log.info("fleet token in use: %s", tok)
            def boot():
                _banner(fleet_token())
            """
        fs = run_interp(files, rules=[sec01])
        assert len(fs) == 1
        f = fs[0]
        assert f.rule == "SEC01"
        assert "logging sink" in f.message
        assert "boot.py::boot -> boot.py::_banner" in f.message

    def test_auth_envelope_negative(self):
        files = dict(self.AUTH)
        files["jepsen_tpu/serve/sign.py"] = """
            import hashlib
            import hmac
            from jepsen_tpu.serve.auth import AUTH_FIELD, fleet_token
            def sign(frame):
                tok = fleet_token()
                mac = hmac.new(tok.encode(), b"payload",
                               hashlib.sha256).hexdigest()
                frame[AUTH_FIELD] = mac
                return frame
            """
        assert run_interp(files, rules=[sec01]) == []

    def test_hmac_outside_envelope_caught(self):
        """The mac is token material: placing it under any key but
        ``auth`` is a leak."""
        files = dict(self.AUTH)
        files["jepsen_tpu/serve/sign.py"] = """
            import hashlib
            import hmac
            def status_snapshot():
                from jepsen_tpu.serve.auth import fleet_token
                tok = fleet_token()
                mac = hmac.new(tok.encode(), b"p",
                               hashlib.sha256).hexdigest()
                return {"type": "status", "mac-debug": mac}
            """
        fs = run_interp(files, rules=[sec01])
        assert len(fs) == 1
        assert "snapshot-payload sink" in fs[0].message
        assert "sign.py::status_snapshot" in fs[0].message

    def test_class_attr_token_into_exception_caught(self):
        files = dict(self.AUTH)
        files["jepsen_tpu/serve/cli.py"] = """
            from jepsen_tpu.serve.auth import fleet_token
            class Client:
                def __init__(self):
                    self._token = fleet_token()
                def fail(self):
                    raise RuntimeError(f"auth rejected: {self._token}")
            """
        fs = run_interp(files, rules=[sec01])
        assert len(fs) == 1
        assert "exception sink" in fs[0].message
        assert "cli.py::Client.fail" in fs[0].message

    def test_existence_check_negative(self):
        files = dict(self.AUTH)
        files["jepsen_tpu/serve/cli.py"] = """
            import logging
            from jepsen_tpu.serve.auth import fleet_token
            log = logging.getLogger(__name__)
            def boot():
                log.info("auth enabled: %s", bool(fleet_token()))
            """
        assert run_interp(files, rules=[sec01]) == []


class TestDl01:
    def test_wall_clock_into_frame_caught(self):
        fs = run_interp({"jepsen_tpu/serve/tx.py": """
            import time
            def send(sock):
                frame = {"type": "submit", "id": 1,
                         "deadline-rem-s": time.time() + 30.0}
                sock.sendall(frame)
            """}, rules=[dl01])
        assert len(fs) == 1
        f = fs[0]
        assert f.rule == "DL01"
        assert "wall-clock reading `time.time()`" in f.message
        assert "tx.py::send" in f.message

    def test_remaining_budget_negative(self):
        assert run_interp({"jepsen_tpu/serve/tx.py": """
            def send(sock, deadline):
                frame = {"type": "submit", "id": 1,
                         "deadline-rem-s": deadline.remaining()}
                sock.sendall(frame)
            """}, rules=[dl01]) == []

    def test_wall_clock_two_frames_up_caught(self):
        fs = run_interp({"jepsen_tpu/serve/tx.py": """
            import time
            def build(deadline_s):
                return {"type": "submit", "id": 1,
                        "deadline-rem-s": deadline_s}
            def mid(d):
                return build(d)
            def top():
                return mid(time.time())
            """}, rules=[dl01])
        assert len(fs) == 1
        assert "tx.py::top -> tx.py::mid -> tx.py::build" in fs[0].message

    def test_bare_monotonic_caught_difference_negative(self):
        fs = run_interp({"jepsen_tpu/serve/tx.py": """
            import time
            def bad(sock):
                frame = {"type": "submit", "id": 1,
                         "deadline-rem-s": time.monotonic() + 5}
                sock.sendall(frame)
            def good(sock, deadline_at):
                frame = {"type": "submit", "id": 2,
                         "deadline-rem-s": deadline_at - time.monotonic()}
                sock.sendall(frame)
            """}, rules=[dl01])
        assert len(fs) == 1
        assert "absolute monotonic" in fs[0].message
        assert "tx.py::bad" in fs[0].message

    def test_submit_frame_without_deadline_caught(self):
        fs = run_interp({"jepsen_tpu/serve/tx.py": """
            def send(sock):
                frame = {"type": "submit", "id": 1}
                sock.sendall(frame)
            """}, rules=[dl01])
        assert len(fs) == 1
        assert "carries no deadline field" in fs[0].message

    def test_non_submit_frame_needs_no_deadline(self):
        assert run_interp({"jepsen_tpu/serve/tx.py": """
            def send(sock):
                frame = {"type": "register", "worker": "w0"}
                sock.sendall(frame)
            """}, rules=[dl01]) == []


# ---------------------------------------------------------------------------
# the Warden tier: RACE01 / ATOM01 / RES01 over the guarded-by inference
# ---------------------------------------------------------------------------

class TestRace01:
    #: one declared lock ('fleet'), one thread seam, one unguarded write
    UNGUARDED = {"jepsen_tpu/serve/fleet.py": """
        import threading
        class Fleet:
            def __init__(self):
                self._lock = threading.Lock()
                self.depth = 0
                threading.Thread(target=self._loop).start()
            def _loop(self):
                with self._lock:
                    self.depth += 1
            def bump(self):
                self.depth = 5
            def view(self):
                return self.depth
        """}

    def test_unguarded_write_caught(self):
        fs = run_interp(self.UNGUARDED, rules=[race01])
        assert len(fs) == 1
        f = fs[0]
        assert f.rule == "RACE01"
        assert "Fleet.depth" in f.message
        assert "no consistent guard" in f.message
        # both racing sides are named, with their lock state
        assert "Fleet.bump" in f.message and "no lock" in f.message

    def test_message_is_line_free(self):
        fs = run_interp(self.UNGUARDED, rules=[race01])
        assert not re.search(r"\d+:\d+|line \d+", fs[0].message)

    def test_lock_held_through_callee_clean(self):
        """The MUST-hold entry set inherits the caller's lock: a helper
        that only ever runs under the lock is guarded, even with no
        lexical ``with`` of its own."""
        assert run_interp({"jepsen_tpu/serve/fleet.py": """
            import threading
            class Fleet:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.depth = 0
                    threading.Thread(target=self._loop).start()
                def _loop(self):
                    with self._lock:
                        self._bump()
                def _bump(self):
                    self.depth += 1
                def view(self):
                    with self._lock:
                        return self.depth
            """}, rules=[race01]) == []

    def test_safe_publication_exempt(self):
        """Writes in __init__ before the first thread start are safe
        publication; a read-only field afterwards needs no lock."""
        assert run_interp({"jepsen_tpu/serve/fleet.py": """
            import threading
            class Fleet:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.mode = "idle"
                    threading.Thread(target=self._loop).start()
                def _loop(self):
                    m = self.mode
                def view(self):
                    return self.mode
            """}, rules=[race01]) == []

    def test_post_spawn_init_write_caught(self):
        """The same write AFTER the thread starts is post-publication
        and unguarded — the ordering inside __init__ is load-bearing."""
        fs = run_interp({"jepsen_tpu/serve/fleet.py": """
            import threading
            class Fleet:
                def __init__(self):
                    self._lock = threading.Lock()
                    threading.Thread(target=self._loop).start()
                    self.mode = "idle"
                def _loop(self):
                    m = self.mode
            """}, rules=[race01])
        assert len(fs) == 1
        assert "Fleet.mode" in fs[0].message

    def test_other_objects_spawn_does_not_publish(self):
        """A callee spawning threads on a DIFFERENT object (a helper
        fleet starting its own loops) does not publish this object:
        writes after such a call are still safe publication."""
        assert run_interp({
            "jepsen_tpu/serve/helper.py": """
                import threading
                class Helper:
                    def __init__(self):
                        threading.Thread(target=self._loop).start()
                    def _loop(self):
                        pass
                """,
            "jepsen_tpu/serve/fleet.py": """
                import threading
                from jepsen_tpu.serve.helper import Helper
                class Fleet:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self.helper = Helper()
                        self.mode = "idle"
                        threading.Thread(target=self._loop).start()
                    def _loop(self):
                        m = self.mode
                    def view(self):
                        return self.mode
                """}, rules=[race01]) == []

    def test_threadsafe_ctor_attr_exempt(self):
        """queue.Queue / Event fields are internally synchronized."""
        assert run_interp({"jepsen_tpu/serve/fleet.py": """
            import queue
            import threading
            class Fleet:
                def __init__(self):
                    self.q = queue.Queue()
                    threading.Thread(target=self._loop).start()
                def _loop(self):
                    self.q.put(1)
                def push(self):
                    self.q = queue.Queue()
            """}, rules=[race01]) == []

    def test_single_root_attr_not_shared(self):
        """No thread seam, no sharing: a single-threaded class needs no
        locks at all."""
        assert run_interp({"jepsen_tpu/serve/fleet.py": """
            class Fleet:
                def __init__(self):
                    self.depth = 0
                def bump(self):
                    self.depth += 1
            """}, rules=[race01]) == []

    def test_pragma_with_reason_suppresses(self):
        files = dict(self.UNGUARDED)
        files["jepsen_tpu/serve/fleet.py"] = files[
            "jepsen_tpu/serve/fleet.py"].replace(
            "self.depth = 5",
            "# lint: disable=RACE01(documented tear contract)\n"
            "        self.depth = 5")
        assert run_interp(files, rules=[race01]) == []


class TestAtom01:
    CHECK_THEN_ACT = {"jepsen_tpu/serve/fleet.py": """
        import threading
        class Fleet:
            def __init__(self):
                self._lock = threading.Lock()
                self.depth = 0
                threading.Thread(target=self._loop).start()
            def _loop(self):
                with self._lock:
                    self.depth += 1
            def maybe_reset(self):
                with self._lock:
                    d = self.depth
                if d > 10:
                    with self._lock:
                        self.depth = 0
        """}

    def test_check_then_act_caught(self):
        fs = run_interp(self.CHECK_THEN_ACT, rules=[atom01])
        assert len(fs) == 1
        f = fs[0]
        assert f.rule == "ATOM01"
        assert "check-then-act on `self.depth`" in f.message
        assert "'fleet'" in f.message

    def test_double_checked_reread_clean(self):
        files = {"jepsen_tpu/serve/fleet.py":
                 self.CHECK_THEN_ACT["jepsen_tpu/serve/fleet.py"].replace(
                     "with self._lock:\n                        "
                     "self.depth = 0",
                     "with self._lock:\n                        "
                     "if self.depth > 10:\n"
                     "                            self.depth = 0")}
        assert run_interp(files, rules=[atom01]) == []

    def test_check_and_act_in_one_section_clean(self):
        assert run_interp({"jepsen_tpu/serve/fleet.py": """
            import threading
            class Fleet:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.depth = 0
                    threading.Thread(target=self._loop).start()
                def _loop(self):
                    with self._lock:
                        self.depth += 1
                def maybe_reset(self):
                    with self._lock:
                        d = self.depth
                        if d > 10:
                            self.depth = 0
            """}, rules=[atom01]) == []

    def test_act_through_callee_caught(self):
        """The act side hiding in a helper that may acquire the lock and
        may write the attr is still a torn decision."""
        fs = run_interp({"jepsen_tpu/serve/fleet.py": """
            import threading
            class Fleet:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.depth = 0
                    threading.Thread(target=self._loop).start()
                def _loop(self):
                    with self._lock:
                        self.depth += 1
                def _reset(self):
                    with self._lock:
                        self.depth = 0
                def maybe_reset(self):
                    with self._lock:
                        d = self.depth
                    if d > 10:
                        self._reset()
            """}, rules=[atom01])
        assert len(fs) == 1
        assert "Fleet._reset" in fs[0].message


class TestRes01:
    REQUEST = {"jepsen_tpu/serve/request.py": """
        class Request:
            def __init__(self, h):
                self.h = h
            def claim_finish(self):
                return True
            def cancel(self):
                pass
        """}

    def test_leaked_on_raise_caught(self):
        files = dict(self.REQUEST)
        files["jepsen_tpu/serve/service.py"] = """
            from jepsen_tpu.serve.request import Request
            def validate(h):
                if not h:
                    raise ValueError("empty")
            def admit(h):
                req = Request(h)
                validate(h)
                req.claim_finish()
                return req
            """
        fs = run_interp(files, rules=[res01])
        assert len(fs) == 1
        f = fs[0]
        assert f.rule == "RES01"
        assert "`req`" in f.message and "Request" in f.message
        assert "validate" in f.message

    def test_finally_resolved_clean(self):
        files = dict(self.REQUEST)
        files["jepsen_tpu/serve/service.py"] = """
            from jepsen_tpu.serve.request import Request
            def validate(h):
                if not h:
                    raise ValueError("empty")
            def admit(h):
                req = Request(h)
                try:
                    validate(h)
                    req.claim_finish()
                finally:
                    req.cancel()
                return req
            """
        assert run_interp(files, rules=[res01]) == []

    def test_hand_off_discharges(self):
        """Passing the object onward moves ownership: the new owner's
        discipline applies, this window closes."""
        files = dict(self.REQUEST)
        files["jepsen_tpu/serve/service.py"] = """
            from jepsen_tpu.serve.request import Request
            def enqueue(req):
                pass
            def validate(h):
                if not h:
                    raise ValueError("empty")
            def admit(h):
                req = Request(h)
                enqueue(req)
                validate(h)
            """
        assert run_interp(files, rules=[res01]) == []

    def test_subclass_ctor_tracked(self):
        files = dict(self.REQUEST)
        files["jepsen_tpu/serve/service.py"] = """
            from jepsen_tpu.serve.request import Request
            class WglRequest(Request):
                pass
            def validate(h):
                if not h:
                    raise ValueError("empty")
            def admit(h):
                req = WglRequest(h)
                validate(h)
                req.claim_finish()
            """
        fs = run_interp(files, rules=[res01])
        assert len(fs) == 1
        assert fs[0].rule == "RES01" and "`req`" in fs[0].message

    def test_catch_all_delegating_to_finalizer_clean(self):
        files = dict(self.REQUEST)
        files["jepsen_tpu/serve/service.py"] = """
            from jepsen_tpu.serve.request import Request
            def validate(h):
                if not h:
                    raise ValueError("empty")
            class Svc:
                def _finalize_all(self):
                    pass
                def admit(self, h):
                    req = Request(h)
                    try:
                        validate(h)
                        req.claim_finish()
                    except Exception:
                        self._finalize_all()
                        raise
                    return req
            """
        assert run_interp(files, rules=[res01]) == []


class TestEnv01:
    PATH = "jepsen_tpu/serve/fixture.py"

    def test_undocumented_knob_caught(self):
        fs = run_rule(env01, """
            import os
            def knob():
                return os.environ.get("JTPU_DEFINITELY_NOT_DOCUMENTED")
            """, self.PATH)
        assert len(fs) == 1
        assert fs[0].rule == "ENV01"
        assert "JTPU_DEFINITELY_NOT_DOCUMENTED" in fs[0].message
        assert "knob" in fs[0].message

    def test_documented_knob_clean(self):
        assert run_rule(env01, """
            import os
            def knob():
                return os.environ.get("JTPU_FISSION_THRESHOLD", "16384")
            """, self.PATH) == []

    def test_placeholder_family_row_matches(self):
        # JEPSEN_TPU_SLO_<NAME> covers any concrete member
        assert run_rule(env01, """
            import os
            def knob():
                return os.environ.get("JEPSEN_TPU_SLO_UNKNOWN_RATE")
            """, self.PATH) == []

    def test_optional_bracket_row_matches_both_forms(self):
        # JEPSEN_TPU_TENANT_QUOTA[_<NAME>]: bare and suffixed
        assert run_rule(env01, """
            import os
            def knobs():
                a = os.environ.get("JEPSEN_TPU_TENANT_QUOTA")
                b = os.environ.get("JEPSEN_TPU_TENANT_QUOTA_ACME")
                return a, b
            """, self.PATH) == []

    def test_all_read_forms_seen(self):
        fs = run_rule(env01, """
            import os
            from os import environ, getenv
            def knobs():
                a = os.environ["JTPU_NOT_DOCUMENTED_A"]
                b = os.getenv("JTPU_NOT_DOCUMENTED_B")
                c = getenv("JTPU_NOT_DOCUMENTED_C")
                d = "JTPU_NOT_DOCUMENTED_D" in os.environ
                e = environ.get("JTPU_NOT_DOCUMENTED_E")
                return a, b, c, d, e
            """, self.PATH)
        assert {re.search(r"JTPU_NOT_DOCUMENTED_[A-E]", f.message).group()
                for f in fs} == {f"JTPU_NOT_DOCUMENTED_{s}"
                                 for s in "ABCDE"}

    def test_computed_name_out_of_scope(self):
        assert run_rule(env01, """
            import os
            def knob(name):
                return os.environ.get("JEPSEN_TPU_" + name.upper())
            """, self.PATH) == []

    def test_non_prefixed_env_ignored(self):
        assert run_rule(env01, """
            import os
            def knob():
                return os.environ.get("HOME")
            """, self.PATH) == []


class TestSarif:
    def test_sarif_fingerprints_match_baseline_keys(self):
        fs = [Finding("SEC01", "jepsen_tpu/serve/x.py", 3, "msg",
                      hint="h"),
              Finding("DL01", "jepsen_tpu/serve/y.py", 0, "msg2",
                      baselined=True)]
        doc = to_sarif(fs)
        assert doc["version"] == "2.1.0"
        run = doc["runs"][0]
        assert [r["id"] for r in run["tool"]["driver"]["rules"]] == [
            "DL01", "SEC01"]
        r0, r1 = run["results"]
        assert r0["level"] == "error" and r1["level"] == "note"
        assert r0["partialFingerprints"]["jepsenTpuLint/v1"] == \
            "SEC01|jepsen_tpu/serve/x.py|msg"
        # SARIF regions are 1-based even when the finding is file-level
        assert r1["locations"][0]["physicalLocation"]["region"][
            "startLine"] == 1


# ---------------------------------------------------------------------------
# the repo itself
# ---------------------------------------------------------------------------

class TestRepoIsClean:
    def test_ast_tier_clean_on_repo(self):
        findings, _ = run_ast_tier()
        assert findings == [], "\n" + "\n".join(f.render() for f in findings)

    def test_interp_tier_clean_on_repo_within_budget(self):
        """The whole interprocedural tier — graph build plus all three
        rules — must stay clean AND inside the CI wall-time budget
        (<60 s on a 1-core runner; we assert a third of that here to
        leave headroom)."""
        start = time.monotonic()
        findings, graph = run_interp_tier()
        elapsed = time.monotonic() - start
        assert findings == [], "\n" + "\n".join(f.render() for f in findings)
        assert elapsed < 20.0, (
            f"interp tier took {elapsed:.1f}s locally; the CI budget "
            f"is 60s on a slower runner")
        # the graph actually covered the repo (guards against a
        # discovery regression silently analyzing nothing)
        assert len(graph.funcs) > 1000
        assert any(e.kind == "thread"
                   for es in graph.out.values() for e in es)

    def test_baseline_is_empty(self):
        assert Baseline.load().entries == [], (
            "the committed baseline must stay empty: fix findings or "
            "justify a pragma instead of baselining new debt")
