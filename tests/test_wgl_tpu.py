"""Device engine vs CPU oracle: differential testing on golden and
synthesized histories (runs on the virtual-CPU jax backend in CI)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jepsen_tpu.checker import wgl_cpu, wgl_tpu
from jepsen_tpu.history import History, INVOKE, OK, FAIL, INFO, Op
from jepsen_tpu.models import CASRegister, Mutex, get_model
from jepsen_tpu.ops.dedup import sort_dedup_compact
from jepsen_tpu.synth import cas_register_history, corrupt_reads


def mk(process, type_, f, value=None):
    return Op(process=process, type=type_, f=f, value=value)


class TestDedup:
    def test_basic(self):
        cols = [jnp.asarray(np.array([3, 1, 3, 2, 1], np.int32))]
        valid = jnp.asarray([True, True, True, True, False])
        out, ov, total, overflow = sort_dedup_compact(cols, valid, 4)
        assert int(total) == 3 and not bool(overflow)
        assert out[0][:3].tolist() == [1, 2, 3]
        assert ov.tolist() == [True, True, True, False]

    def test_multi_column(self):
        c0 = jnp.asarray(np.array([1, 1, 1, 2], np.uint32))
        c1 = jnp.asarray(np.array([5, 5, 6, 5], np.int32))
        out, ov, total, overflow = sort_dedup_compact([c0, c1],
                                                      jnp.ones(4, bool), 8)
        assert int(total) == 3

    def test_overflow(self):
        cols = [jnp.arange(10, dtype=jnp.int32)]
        out, ov, total, overflow = sort_dedup_compact(cols, jnp.ones(10, bool), 4)
        assert bool(overflow) and int(total) == 10
        assert out[0].tolist() == [0, 1, 2, 3]

    def test_all_invalid(self):
        cols = [jnp.zeros(6, jnp.int32)]
        out, ov, total, overflow = sort_dedup_compact(cols, jnp.zeros(6, bool), 4)
        assert int(total) == 0 and not bool(overflow)
        assert ov.tolist() == [False] * 4

    def test_multipass_path_matches_variadic(self, monkeypatch):
        """Force the narrow multi-pass sort (used above WIDE_SORT_ROWS, the
        regime where one wide variadic sort crashes the TPU worker) and check
        it is bit-identical to the variadic path — including ghost
        subsumption and the new_rows fixpoint signal."""
        from jepsen_tpu.ops import dedup
        rng = np.random.default_rng(7)
        n = 512
        cols = [jnp.asarray(rng.integers(0, 6, n).astype(np.uint32)),
                jnp.asarray(rng.integers(-3, 3, n).astype(np.int32))]
        # small ghost universe so subset relations actually occur
        gcols = [jnp.asarray(rng.integers(0, 8, n).astype(np.uint32))]
        valid = jnp.asarray(rng.random(n) < 0.7)
        origin = jnp.asarray((rng.random(n) < 0.5).astype(np.int32))
        ref = sort_dedup_compact(cols, valid, 64, ghost_cols=gcols,
                                 origin=origin)
        monkeypatch.setattr(dedup, "WIDE_SORT_ROWS", 1)
        got = sort_dedup_compact(cols, valid, 64, ghost_cols=gcols,
                                 origin=origin)
        for a, b in zip(ref[0], got[0]):
            assert a.tolist() == b.tolist()
        assert ref[1].tolist() == got[1].tolist()
        assert int(ref[2]) == int(got[2])
        assert bool(ref[3]) == bool(got[3])
        assert bool(ref[4]) == bool(got[4])


def _np_sort_dedup(cols, valid, capacity, ghost, origin=None):
    """``sort_dedup_compact`` with ghost columns, in plain NumPy: lexsort,
    each row's head as the first row of its (inv, cols) group, the subset
    test word-wise against the head and against the rows ``off`` before it
    in the same group, then the kept rows in order."""
    from jepsen_tpu.ops import dedup
    n = len(valid)
    keys = [(~valid).astype(np.int32)] + list(cols) + list(ghost)
    order = np.lexsort(tuple(reversed(keys)))
    s_valid = valid[order]
    s_cols = [c[order] for c in cols]
    s_ghost = [g[order] for g in ghost]
    idx = np.arange(n)
    starts = np.ones(n, bool)
    starts[1:] = (s_valid[1:] != s_valid[:-1]) | np.any(
        [c[1:] != c[:-1] for c in s_cols], axis=0)
    head = np.maximum.accumulate(np.where(starts, idx, 0))
    in_group = s_valid & (head != idx)

    def subset_of(at):
        return np.all([(g[at] & ~g) == 0 for g in s_ghost], axis=0)

    drop = in_group & subset_of(head)
    for off in (1, 2, 4, 8, 16)[:dedup.N_PROBES]:
        at = np.maximum(idx - off, 0)
        drop |= in_group & (idx - off >= head) & subset_of(at)
    keep = s_valid & ~drop
    total = np.int32(keep.sum())

    def fit(c):
        out = np.zeros(capacity, c.dtype)
        out[:min(total, capacity)] = c[keep][:capacity]
        return out

    out = ([fit(c) for c in s_cols + s_ghost],
           np.arange(capacity) < total, total, total > capacity)
    if origin is None:
        return out
    s_origin = origin[order]
    return out + (np.any(keep & (s_origin == 1)), fit(s_origin))


def _probe_case(kind, n, G, seed):
    """cols, valid, ghost columns, origin: random rows in few groups with a
    small ghost universe, so that subsets occur; ``kind`` bends one of
    them."""
    rng = np.random.default_rng([seed, n, G])
    cols = [rng.integers(0, max(2, n // 12), n).astype(np.uint32),
            rng.integers(-2, 2, n).astype(np.int32)]
    valid = rng.random(n) < 0.7
    if kind == "all_valid":
        valid[:] = True
    elif kind == "none_valid":
        valid[:] = False
    elif kind == "one_group":
        cols = [np.full(n, 3, np.uint32), np.full(n, -1, np.int32)]
    elif kind == "spanning_group":
        cols = [np.full(n, 3, np.uint32), np.full(n, -1, np.int32)]
        valid[:] = True
    elif kind == "singletons":
        cols[0] = rng.permutation(n).astype(np.uint32)
    ghost = [rng.integers(0, 16, n).astype(np.uint32) for _ in range(G)]
    origin = (rng.random(n) < 0.5).astype(np.int32)
    return cols, valid, ghost, origin


def _same_arrays(got, want):
    got, want = (jax.tree_util.tree_leaves(x) for x in (got, want))
    assert len(got) == len(want)
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(g, w)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _dedup_jit(capacity, with_origin, cols, valid, ghost, origin):
    return sort_dedup_compact(cols, valid, capacity, ghost_cols=ghost,
                              origin=origin if with_origin else None)


class TestHeadProbe:
    """The subsumption probe reads its group head's ghost words from a
    scan along the sorted rows: every array ``sort_dedup_compact`` returns
    equals the NumPy reference's."""

    KINDS = ("random", "all_valid", "none_valid", "one_group",
             "spanning_group", "singletons")

    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("with_origin", [False, True])
    @pytest.mark.parametrize("n", [8, 512, 1536, 6144])
    @pytest.mark.parametrize("G", [1, 2])
    def test_equals_the_numpy_reference(self, G, n, with_origin, kind):
        cols, valid, ghost, origin = _probe_case(kind, n, G, 30)
        capacity = max(4, n // 2)
        want = _np_sort_dedup(cols, valid, capacity, ghost,
                              origin if with_origin else None)
        _same_arrays(_dedup_jit(capacity, with_origin, cols, valid, ghost,
                                origin), want)
        if kind == "random" and n >= 512:
            # the case is worth its name: heads and offsets both hit
            assert want[2] < valid.sum() and want[2] > 0

    @pytest.mark.parametrize("n", [8, 512])
    @pytest.mark.parametrize("G", [1, 2])
    def test_under_vmap_lanes_of_different_kinds(self, G, n):
        lanes = [_probe_case(kind, n, G, 31) for kind in
                 ("random", "none_valid", "spanning_group", "singletons")]
        batched = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *lanes)
        got = jax.jit(jax.vmap(functools.partial(_dedup_jit, n // 2, True))
                      )(*batched)
        for i, (cols, valid, ghost, origin) in enumerate(lanes):
            _same_arrays(jax.tree_util.tree_map(lambda x: x[i], got),
                         _np_sort_dedup(cols, valid, n // 2, ghost, origin))

    @pytest.mark.parametrize("kind", ["random", "spanning_group"])
    @pytest.mark.parametrize("G", [1, 2])
    def test_wide_sort_branch(self, G, kind, monkeypatch):
        from jepsen_tpu.ops import dedup
        monkeypatch.setattr(dedup, "WIDE_SORT_ROWS", 1)
        cols, valid, ghost, origin = _probe_case(kind, 512, G, 32)
        _same_arrays(
            sort_dedup_compact(cols, valid, 200, ghost_cols=ghost,
                               origin=origin),
            _np_sort_dedup(cols, valid, 200, ghost, origin))

    @pytest.mark.parametrize("n", [1, 2, 3, 5, 8, 100, 513])
    def test_head_words_is_the_nearest_head_at_or_before(self, n):
        from jepsen_tpu.ops.dedup import head_words
        rng = np.random.default_rng(n)
        is_head = rng.random(n) < 0.3
        is_head[0] = True
        cols = [rng.integers(0, 2**32, n, dtype=np.uint32) for _ in "ab"]
        head = np.maximum.accumulate(np.where(is_head, np.arange(n), 0))
        _same_arrays(head_words(jnp.asarray(is_head), cols),
                     [c[head] for c in cols])

    @pytest.mark.parametrize("G", [1, 2])
    @pytest.mark.parametrize("vmapped", [False, True],
                             ids=["plain", "vmapped"])
    def test_lowers_to_two_sorts_and_no_gather(self, vmapped, G):
        """What holds the gain: no gather anywhere in the dedup, and two
        sorts a merge (the lexicographic one and ``compact_rows``'s), not
        the three of the head-index form."""
        args = _probe_case("random", 512, G, 33)
        f = functools.partial(_dedup_jit, 256, True)
        if vmapped:
            f = jax.vmap(f)
            args = jax.tree_util.tree_map(lambda x: jnp.stack([x] * 4),
                                          args)
        text = jax.jit(f).lower(*args).as_text()
        assert "gather" not in text
        assert text.count("stablehlo.sort") == 2

    def test_the_chip_tool_runs_and_its_forms_agree(self):
        tool = _script("probe_bench")
        for lanes in (0, 2):
            for what in ("probe", "merge"):
                rows = [tool.bench(256, 2, lanes, 2, form, what)
                        for form in tool.FORMS]
                assert len({r["checksum"] for r in rows}) == 1, rows

    @pytest.mark.parametrize("refuted", [False, True],
                             ids=["valid", "refuted"])
    def test_a_history_without_ghosts_never_enters_the_probe(
            self, refuted, monkeypatch):
        """The lean engine (no ``:info``, so no ghost column) is the bypass:
        its search is the parent's to the count (recorded at PR 29's tree,
        CPU backend), and the head scan is never traced."""
        from jepsen_tpu.checker.prep import prepare
        from jepsen_tpu.ops import dedup
        model = get_model("cas-register")
        h = cas_register_history(300, concurrency=6, crash_p=0.0,
                                 seed=12 if refuted else 11)
        if refuted:
            h = corrupt_reads(h, n=1, seed=3)
        assert wgl_tpu.chosen_gwords(prepare(h, model)) == 0

        def never(is_head, cols):
            raise AssertionError("the lean engine traced the head probe")

        monkeypatch.setattr(dedup, "head_words", never)
        TestCompactionInTheEngine._fresh_engines(monkeypatch)
        r = wgl_tpu.check(model, h, capacity=128, chunk=64)
        want = ({"valid": False, "configs-explored": 1469}
                if refuted else
                {"valid": True, "configs-explored": 5539,
                 "closure-rounds": 514})
        want.update({"analyzer": "wgl-tpu", "capacity": 512,
                     "max-capacity-reached": 512, "window": 6})
        assert {k: r[k] for k in want} == want
        if refuted:
            assert r["op"]["index"] == 215
            assert r["witness"]["valid"] is False
            assert r["witness"]["op"]["index"] == 215
        else:
            assert set(r) == set(want)


class TestCompactRows:
    def test_matches_kept_rows_in_order(self):
        from jepsen_tpu.ops.dedup import compact_rows
        rng = np.random.default_rng(3)
        n = 97
        keep = rng.random(n) < 0.4
        col1 = rng.integers(0, 100, n).astype(np.int32)
        col2 = rng.integers(0, 9, (n, 3)).astype(np.uint32)
        (o1, o2), ov, total = compact_rows(
            [jnp.asarray(col1), jnp.asarray(col2)], jnp.asarray(keep), 64)
        want1 = col1[keep]
        assert int(total) == len(want1)
        assert o1[:len(want1)].tolist() == want1.tolist()
        assert o2[:len(want1)].tolist() == col2[keep].tolist()
        assert not bool(ov[len(want1)]) if len(want1) < 64 else True
        assert np.all(np.asarray(o1[len(want1):]) == 0)

    def test_truncates_past_capacity(self):
        from jepsen_tpu.ops.dedup import compact_rows
        col = jnp.arange(10, dtype=jnp.int32)
        (o,), ov, total = compact_rows([col], jnp.ones(10, bool), 4)
        assert int(total) == 10 and o.tolist() == [0, 1, 2, 3]

    def test_wide_fallback_matches(self, monkeypatch):
        from jepsen_tpu.ops import dedup
        rng = np.random.default_rng(5)
        n = 256
        keep = jnp.asarray(rng.random(n) < 0.5)
        cols = [jnp.asarray(rng.integers(0, 50, n).astype(np.int32)),
                jnp.asarray(rng.integers(0, 7, (n, 2)).astype(np.uint32))]
        ref = dedup.compact_rows(cols, keep, 96)
        monkeypatch.setattr(dedup, "WIDE_SORT_ROWS", 1)
        got = dedup.compact_rows(cols, keep, 96)
        for a, b in zip(ref[0], got[0]):
            assert a.tolist() == b.tolist()
        assert ref[1].tolist() == got[1].tolist()
        assert int(ref[2]) == int(got[2])


def _grid_case(C, W, density, seed, model):
    """A random [C, W] candidate grid: the engine's inputs, and the two
    flattened columns the sort used to carry."""
    rng = np.random.default_rng(seed)
    MW, S = (W + 31) // 32, model.state_size
    mask = rng.integers(0, 2**32, (C, MW), dtype=np.uint32)
    states = rng.integers(0, 5, (C, S)).astype(np.int32)
    win_ops = np.zeros((W, 6), np.int32)
    win_ops[:, 0] = rng.integers(0, 3, W)
    win_ops[:, 1:3] = rng.integers(0, 5, (W, 2))
    cv = rng.random((C, W)) < density
    return tuple(jnp.asarray(x) for x in (mask, states, win_ops, cv))


@functools.lru_cache(maxsize=None)
def _script(name):
    """scripts/<name>.py as a module: ``compact_bench`` holds the one copy
    of the compaction as it was before rank and select
    (``sort_compaction``), ``probe_bench`` the head probe's gathers."""
    import importlib.util
    import pathlib
    path = pathlib.Path(__file__).resolve().parents[1] / "scripts" \
        / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sort_compaction(step, mask, states, win_ops, cv, NC, form=None):
    """The reference: every cell's row, then one stable sort of all C*W of
    them (``compact_rows``); ``form`` is taken and ignored so that it can
    stand in for ``compact_candidates`` inside an engine."""
    return _script("compact_bench").sort_compaction(step, mask, states, win_ops,
                                            cv, NC)


class TestCompactGrid:
    """Candidate compaction by rank and select is ``compact_rows`` on the
    flattened grid, bit for bit: columns, ``valid`` and ``total``."""

    FORMS = ("matmul", "blocks")
    new = staticmethod(jax.jit(wgl_tpu.compact_candidates,
                               static_argnums=(0, 5, 6)))
    old = staticmethod(jax.jit(sort_compaction, static_argnums=(0, 5)))

    @staticmethod
    def _same(got, want):
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and g.shape == w.shape
            assert np.array_equal(np.asarray(g), np.asarray(w))

    @pytest.mark.parametrize("density", [0.0, 0.01, 0.2, 1.0])
    @pytest.mark.parametrize("W", [8, 12, 45, 60])
    def test_equals_the_sort_on_the_flattened_grid(self, W, density):
        model = get_model("cas-register")
        args = _grid_case(24, W, density, 1000 * W + int(100 * density),
                          model)
        total = int(args[3].sum())
        # NC below, at and above the count of valid cells
        for NC in sorted({max(1, total // 2), max(1, total), total + 7}):
            want = self.old(model.step, *args, NC)
            assert int(want[3]) == total
            for form in self.FORMS:
                self._same(self.new(model.step, *args, NC, form), want)

    @pytest.mark.parametrize("form", FORMS)
    def test_state_of_several_lanes(self, form):
        # multi-register: 3 state lanes ride the fetch as 3 columns
        model = get_model("multi-register")
        args = _grid_case(37, 20, 0.3, 5, model)
        self._same(self.new(model.step, *args, 64, form),
                   self.old(model.step, *args, 64))

    @pytest.mark.parametrize("form", FORMS)
    def test_under_vmap_lanes_of_different_density(self, form):
        model = get_model("cas-register")
        lanes = [_grid_case(32, 12, d, i, model)
                 for i, d in enumerate([0.0, 0.05, 1.0])]
        batched = tuple(jnp.stack(x) for x in zip(*lanes))
        got = jax.jit(jax.vmap(lambda m, s, o, c: wgl_tpu.compact_candidates(
            model.step, m, s, o, c, 40, form)))(*batched)
        for i, lane in enumerate(lanes):
            self._same([g[i] for g in got],
                       self.old(model.step, *lane, 40))

    def test_the_chip_tool_runs_and_its_forms_agree(self):
        tool = _script("compact_bench")
        rows = [tool.bench(get_model("cas-register"), 32, 12, 16, lanes,
                           form, 2, 0.3)
                for lanes in (0, 2) for form in tool.FORMS]
        assert len({r["checksum"] for r in rows[:3]}) == 1
        assert len({r["checksum"] for r in rows[3:]}) == 1
        assert all(r["candidates_per_round"] > 16 for r in rows)

    def test_form_by_capacity(self):
        assert [wgl_tpu.compaction_form(c)
                for c in (256, 4096, 8192, 16384, 65536)] == \
            ["matmul"] * 3 + ["blocks"] * 2


class TestCompactionInTheEngine:
    """The engines with rank and select against the same engines with the
    old sort, and against the host oracle, on ghost-bearing histories (an
    engine's ``configs-explored`` is its own count, not the oracle's: the
    old compaction is what it has to equal)."""

    @staticmethod
    def _refuted(seed):
        return corrupt_reads(cas_register_history(
            120, concurrency=8, crash_p=0.04, seed=seed), n=1, seed=seed)

    @staticmethod
    def _fresh_engines(monkeypatch):
        # engines are cached by shape: a patched compaction needs new ones
        from collections import OrderedDict
        from jepsen_tpu.engine import cache
        monkeypatch.setattr(cache.CACHE, "_d", OrderedDict())

    @staticmethod
    def _agree(got, want, host):
        assert got["valid"] is want["valid"] is host["valid"]
        assert got["configs-explored"] == want["configs-explored"]
        assert got.get("op", {}).get("index") == \
            want.get("op", {}).get("index") == \
            host.get("op", {}).get("index")

    def test_single_history_engine_in_all_three_widths(self, monkeypatch):
        seen = []
        real = wgl_tpu.compact_candidates

        def spy(step, mask, states, win_ops, cv, NC, form):
            out = real(step, mask, states, win_ops, cv, NC, form)
            jax.debug.callback(
                lambda t, C=cv.shape[0]: seen.append((NC, C, int(t))),
                out[3])
            return out

        model, h = get_model("cas-register"), self._refuted(16)
        self._fresh_engines(monkeypatch)
        monkeypatch.setattr(wgl_tpu, "compact_candidates", spy)
        got = wgl_tpu.check(model, h, capacity=128, chunk=64)
        jax.effects_barrier()
        self._fresh_engines(monkeypatch)
        monkeypatch.setattr(wgl_tpu, "compact_candidates", sort_compaction)
        want = wgl_tpu.check(model, h, capacity=128, chunk=64)
        self._agree(got, want, wgl_cpu.check(CASRegister(), h))
        assert got["valid"] is False and got["op"]["index"] == 88
        # a round ran in each compacted width: C/2, C and 4C
        ran = {(2 * NC) // C for NC, C, total in seen if total > 0}
        assert ran >= {1, 2, 8}, sorted(ran)

    def test_check_batch(self, monkeypatch):
        from jepsen_tpu.parallel import check_batch
        model = get_model("cas-register")
        hs = [self._refuted(16), self._refuted(7),
              cas_register_history(120, concurrency=8, crash_p=0.04,
                                   seed=8)]
        self._fresh_engines(monkeypatch)
        got = check_batch(model, hs, capacity=128)
        self._fresh_engines(monkeypatch)
        monkeypatch.setattr(wgl_tpu, "compact_candidates", sort_compaction)
        want = check_batch(model, hs, capacity=128)
        assert [g["valid"] for g in got] == [False, False, True]
        for h, g, w in zip(hs, got, want):
            self._agree(g, w, wgl_cpu.check(CASRegister(), h))


class TestLeanEngine:
    """gwords=0 drops the whole ghost-subsumption pipeline; subsumption is
    an optimization, so verdicts must be identical — only configs-explored
    may grow.  chosen_gwords picks lean only for ghost-free histories
    (LEAN_GHOST_MAX=0 default: measured on hardware, even 4 unsubsumed
    ghosts ballooned the 10k-op easy history 819k -> 2.2M configs)."""

    def test_chosen_gwords_default(self):
        from jepsen_tpu.checker.prep import prepare
        model = get_model("cas-register")
        clean = cas_register_history(200, concurrency=4, crash_p=0.0,
                                     seed=1)
        assert wgl_tpu.chosen_gwords(prepare(clean, model)) == 0
        ghosty = cas_register_history(300, concurrency=4, crash_p=0.05,
                                      seed=1)
        p = prepare(ghosty, model)
        assert p.n_ghosts > 0
        assert wgl_tpu.chosen_gwords(p) == wgl_tpu.ghost_words(p)

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_lean_matches_full_with_ghosts(self, seed, monkeypatch):
        # Force lean even for ghost histories: verdicts must still agree
        # with the full engine and the CPU oracle.
        model = get_model("cas-register")
        h = cas_register_history(150, concurrency=4, crash_p=0.03,
                                 seed=seed)
        full = wgl_tpu.check(model, h, capacity=128, chunk=32,
                             max_capacity=4096)
        monkeypatch.setattr(wgl_tpu, "LEAN_GHOST_MAX", 10**9)
        # Without subsumption the ghost pileup needs real capacity
        # headroom (that blowup is exactly why LEAN_GHOST_MAX is 0).
        lean = wgl_tpu.check(model, h, capacity=128, chunk=32,
                             max_capacity=65536)
        assert lean["valid"] == full["valid"]
        oracle = wgl_cpu.check(CASRegister(), h)
        assert lean["valid"] == oracle["valid"]

    def test_lean_refutation(self, monkeypatch):
        monkeypatch.setattr(wgl_tpu, "LEAN_GHOST_MAX", 10**9)
        model = get_model("cas-register")
        h = corrupt_reads(cas_register_history(200, concurrency=4,
                                               crash_p=0.02, seed=9),
                          n=1, seed=2)
        r = wgl_tpu.check(model, h, capacity=128, chunk=32,
                          max_capacity=4096)
        assert r["valid"] is False


CASES = [
    # (ops, expected_valid)
    ([mk(0, INVOKE, "write", 1), mk(0, OK, "write", 1),
      mk(0, INVOKE, "read"), mk(0, OK, "read", 1)], True),
    ([mk(0, INVOKE, "write", 1), mk(0, OK, "write", 1),
      mk(0, INVOKE, "write", 2), mk(0, OK, "write", 2),
      mk(0, INVOKE, "read"), mk(0, OK, "read", 1)], False),
    ([mk(0, INVOKE, "write", 1),
      mk(1, INVOKE, "write", 2),
      mk(0, OK, "write", 1),
      mk(1, OK, "write", 2),
      mk(2, INVOKE, "read"), mk(2, OK, "read", 1)], True),
    ([mk(0, INVOKE, "write", 1), mk(0, OK, "write", 1),
      mk(1, INVOKE, "write", 2), mk(1, INFO, "write", 2),
      mk(2, INVOKE, "read"), mk(2, OK, "read", 2),
      mk(2, INVOKE, "cas", [2, 3]), mk(2, OK, "cas", [2, 3]),
      mk(2, INVOKE, "cas", [2, 4]), mk(2, OK, "cas", [2, 4])], False),
    ([mk(0, INVOKE, "cas", [0, 1]), mk(0, FAIL, "cas", [0, 1]),
      mk(0, INVOKE, "read"), mk(0, OK, "read", None)], True),
]


class TestDeviceEngine:
    @pytest.mark.parametrize("i", range(len(CASES)))
    def test_golden_cases(self, i):
        ops, expect = CASES[i]
        model = get_model("cas-register")
        r = wgl_tpu.check(model, History(ops), capacity=64, chunk=16)
        assert r["valid"] is expect, r

    def test_refutation_reports_op_and_witness(self):
        model = get_model("cas-register")
        h = History([
            mk(0, INVOKE, "write", 1), mk(0, OK, "write", 1),
            mk(0, INVOKE, "read"), mk(0, OK, "read", 9),
        ])
        r = wgl_tpu.check(model, h, capacity=64, chunk=16)
        assert r["valid"] is False
        assert r["op"]["value"] == 9
        assert r["witness"]["valid"] is False

    def test_mutex_model(self):
        model = get_model("mutex")
        h = History([
            mk(0, INVOKE, "acquire"), mk(0, OK, "acquire"),
            mk(1, INVOKE, "acquire"), mk(1, OK, "acquire"),
        ])
        assert wgl_tpu.check(model, h, capacity=64, chunk=16)["valid"] is False

    def test_capacity_retry_path(self):
        # capacity 32 is too small for 6 concurrent writes (~200 distinct
        # configurations); engine must retry with a bigger buffer (8x -> 256,
        # reusing the engine other tests compiled) and still conclude.
        model = get_model("cas-register")
        ops = []
        for i in range(6):
            ops.append(mk(i, INVOKE, "write", i))
        for i in range(6):
            ops.append(mk(i, OK, "write", i))
        ops += [mk(7, INVOKE, "read"), mk(7, OK, "read", 3)]
        r = wgl_tpu.check(model, History(ops), capacity=32, chunk=256)
        assert r["valid"] is True


class TestDifferential:
    @pytest.mark.parametrize("seed", range(8))
    def test_matches_oracle_valid(self, seed):
        h = cas_register_history(250, concurrency=6, crash_p=0.01, seed=seed)
        model = get_model("cas-register")
        cpu = wgl_cpu.check(CASRegister(), h)
        tpu = wgl_tpu.check(model, h, capacity=256, chunk=256)
        assert cpu["valid"] == tpu["valid"] is True

    @pytest.mark.parametrize("seed", range(8))
    def test_matches_oracle_invalid(self, seed):
        h = corrupt_reads(
            cas_register_history(250, concurrency=6, crash_p=0.0, seed=seed),
            n=1, seed=seed)
        model = get_model("cas-register")
        cpu = wgl_cpu.check(CASRegister(), h)
        tpu = wgl_tpu.check(model, h, capacity=256, chunk=256)
        assert cpu["valid"] == tpu["valid"] is False
        assert cpu["op"]["index"] == tpu["op"]["index"]

    @pytest.mark.parametrize("seed", [0, 1])
    def test_stale_swap_differential(self, seed):
        # Swap two read values (may or may not stay linearizable) — engines
        # must agree either way.
        import random
        rng = random.Random(seed)
        h = cas_register_history(150, concurrency=5, crash_p=0.0, seed=seed)
        ops = list(h)
        reads = [i for i, o in enumerate(ops) if o.type == OK and o.f == "read"]
        i, j = rng.sample(reads, 2)
        ops[i], ops[j] = (ops[i].with_(value=ops[j].value),
                          ops[j].with_(value=ops[i].value))
        h2 = History(ops, reindex=True)
        cpu = wgl_cpu.check(CASRegister(), h2)
        tpu = wgl_tpu.check(get_model("cas-register"), h2,
                            capacity=256, chunk=256)
        assert cpu["valid"] == tpu["valid"]


class TestClosureWorkBudget:
    """The per-chunk closure budget (watchdog mitigation): with a tiny
    budget the driver must take many mid-chunk resumes and still reach
    exactly the oracle's verdict."""

    def test_tiny_budget_same_verdicts(self, monkeypatch):
        from jepsen_tpu.checker import wgl_tpu
        monkeypatch.setattr(wgl_tpu, "CLOSURE_WORK_BUDGET", 64)
        model = get_model("cas-register")
        h = cas_register_history(300, concurrency=6, crash_p=0.01, seed=3)
        r = wgl_tpu.check(model, h, capacity=64, chunk=64)
        assert r["valid"] is True, r
        bad = corrupt_reads(h, n=1, seed=3)
        r2 = wgl_tpu.check(model, bad, capacity=64, chunk=64, explain=False)
        assert r2["valid"] is False, r2
        # differential: failing op agrees with the CPU oracle
        c = wgl_cpu.check(CASRegister(), bad)
        assert r2["op"]["index"] == c["op"]["index"]

    def test_budget_scales_with_capacity(self):
        from jepsen_tpu.checker.wgl_tpu import closure_budget
        assert closure_budget(1024) > closure_budget(16384) >= 16

    def test_register_ghost_pileup_collapses_to_antichain(self):
        # A register's state only remembers the last linearized value, so
        # subset subsumption collapses a crashed-write pileup to an O(k)
        # antichain — the delta closure concludes where the round-3 eager
        # closure overflowed.  (This is why the bench ceiling tier moved
        # to the bitset model.)
        from jepsen_tpu.synth import cas_register_history, ghost_write_burst
        model = get_model("cas-register")
        h = History(ghost_write_burst(10)
                    + list(cas_register_history(60, concurrency=4,
                                                crash_p=0.0, seed=3)),
                    reindex=True)
        r = wgl_tpu.check(model, h, capacity=256, chunk=64,
                          max_capacity=4096)
        assert r["valid"] is True, r
        assert r["max-capacity-reached"] <= 1024, r

    def test_bitset_differential_with_host_oracle(self):
        # The bitset model's host-tier oracle (BitSetModel): device and
        # CPU engines must agree on membership-read histories, including
        # a corrupted present-claim.
        from jepsen_tpu.history import INVOKE, OK, Op
        from jepsen_tpu.models.collections import BitSetModel
        model = get_model("bitset-256")

        def ops(*specs):
            out = []
            for p, f, v in specs:
                out.append(Op(process=p, type=INVOKE, f=f, value=v))
                out.append(Op(process=p, type=OK, f=f, value=v))
            return out

        good = History(ops((0, "add", 3), (1, "add", 9),
                           (0, "read", (3, 1)), (1, "read", (5, 0))))
        r = wgl_tpu.check(model, good, capacity=32, chunk=16)
        c = wgl_cpu.check(BitSetModel(), good)
        assert r["valid"] == c["valid"] is True, (r, c)
        bad = History(ops((0, "add", 3), (0, "read", (5, 1))))
        r2 = wgl_tpu.check(model, bad, capacity=32, chunk=16,
                           explain=False)
        c2 = wgl_cpu.check(BitSetModel(), bad)
        assert r2["valid"] == c2["valid"] is False, (r2, c2)
        assert r2["op"]["index"] == c2["op"]["index"]

    def test_bitset_ghost_pileup_is_incompressible(self):
        # The bitset's state IS the linearized subset: 2^k genuinely
        # distinct configurations that no subsumption can merge — the
        # capacity ceiling degrades to unknown (the ceiling tier's claim).
        from jepsen_tpu.synth import bitset_ceiling_history
        model = get_model("bitset-256")
        h = bitset_ceiling_history(12, n_clean=60)
        r = wgl_tpu.check(model, h, capacity=128, chunk=64,
                          max_capacity=1024)
        assert r["valid"] == "unknown", r
        # and a small pileup concludes once capacity covers 2^k
        h6 = bitset_ceiling_history(6, n_clean=60)
        r6 = wgl_tpu.check(model, h6, capacity=256, chunk=64,
                           max_capacity=4096)
        assert r6["valid"] is True, r6

    def test_mutex_differential_random(self):
        # Delta-closure soundness on a second model family: random lock
        # histories from a simulated correct lock service must verify, and
        # a double-granted acquire must refute — both agreeing with the
        # CPU oracle.  (The CAS differential suite can't exercise the
        # mutex step function's refusal patterns.)
        import random as _random
        from jepsen_tpu.history import INVOKE, OK, Op

        def mutex_history(sessions, procs, seed, corrupt=False):
            rng = _random.Random(seed)
            ops, holder, waiting = [], None, []
            pending = {p: 0 for p in range(procs)}  # 0 idle 1 wait 2 held
            remaining = sessions
            while remaining > 0 or holder is not None or waiting:
                choices = []
                if remaining > 0:
                    idle = [p for p in pending if pending[p] == 0]
                    if idle:
                        choices.append("invoke")
                if holder is None and waiting:
                    choices.append("grant")
                if holder is not None:
                    choices.append("release")
                act = rng.choice(choices)
                if act == "invoke":
                    p = rng.choice([p for p in pending if pending[p] == 0])
                    ops.append(Op(process=p, type=INVOKE, f="acquire"))
                    pending[p] = 1
                    waiting.append(p)
                    remaining -= 1
                elif act == "grant":
                    p = waiting.pop(0)
                    ops.append(Op(process=p, type=OK, f="acquire"))
                    pending[p] = 2
                    holder = p
                    if corrupt and waiting and rng.random() < 0.5:
                        # the bug: grant a second waiter while held
                        q = waiting.pop(0)
                        ops.append(Op(process=q, type=OK, f="acquire"))
                        pending[q] = 2
                else:  # release
                    p = holder
                    ops.append(Op(process=p, type=INVOKE, f="release"))
                    ops.append(Op(process=p, type=OK, f="release"))
                    pending[p] = 0
                    holder = None
            return History(ops)

        model = get_model("mutex")
        from jepsen_tpu.models.collections import Mutex
        for seed in range(6):
            h = mutex_history(30, 4, seed)
            r = wgl_tpu.check(model, h, capacity=64, chunk=64)
            c = wgl_cpu.check(Mutex(), h)
            assert r["valid"] == c["valid"] is True, (seed, r, c)
        bad = mutex_history(30, 4, 99, corrupt=True)
        r = wgl_tpu.check(model, bad, capacity=64, chunk=64, explain=False)
        c = wgl_cpu.check(Mutex(), bad)
        assert r["valid"] == c["valid"] is False, (r, c)

    def test_mid_closure_pause_resume(self, monkeypatch):
        # Budget of ONE fixpoint iteration per dispatch: every closure
        # needing more must pause mid-closure (partial set kept, dirty
        # stays, event unconsumed, cl_iters persisted) and the host resumes
        # the same RETURN across dispatches until convergence.  Verdicts —
        # including the refuting op — must match the CPU oracle exactly.
        from jepsen_tpu.checker import wgl_tpu
        monkeypatch.setattr(wgl_tpu, "CLOSURE_WORK_BUDGET", -101)  # cache key
        monkeypatch.setattr(wgl_tpu, "closure_budget", lambda cap: 1)
        model = get_model("cas-register")
        h = cas_register_history(200, concurrency=6, crash_p=0.02, seed=5)
        r = wgl_tpu.check(model, h, capacity=64, chunk=64)
        c = wgl_cpu.check(CASRegister(), h)
        assert r["valid"] == c["valid"], (r, c)
        bad = corrupt_reads(h, n=1, seed=5)
        r2 = wgl_tpu.check(model, bad, capacity=64, chunk=64, explain=False)
        c2 = wgl_cpu.check(CASRegister(), bad)
        assert r2["valid"] is False, r2
        assert r2["op"]["index"] == c2["op"]["index"]


class TestMultiRegisterDevice:
    """Device-tier multi-register (round-5): k int32 lanes, multi-key ops
    packed into (mask, values) int32 fields.  Differential vs the host
    MultiRegister oracle on BASELINE-config-#4/#5-shaped histories."""

    def _model(self, keys=3):
        return get_model("multi-register", keys=keys, vbits=4)

    def test_encoding_roundtrip(self):
        m = self._model()
        f, a, b = m.encode_op(mk(0, INVOKE, "write", [[0, 3], [2, 1]]))
        assert f == 1 and a == 0b101 and b == (3 | (1 << 8))
        f, a, b = m.encode_op(mk(0, OK, "read", [[1, None], [2, 7]]))
        assert f == 0 and a == 0b100 and b == (7 << 8)

    def test_nil_read_encodes_unconstrained(self):
        from jepsen_tpu.models.base import UNKNOWN32
        m = self._model()
        f, a, b = m.encode_op(mk(0, INVOKE, "read", [[0, None], [1, None]]))
        assert a == UNKNOWN32

    def test_judge_minimal_case_on_device(self):
        ops = [
            mk(0, INVOKE, "write", [[0, 1]]),
            mk(0, OK, "write", [[0, 1]]),
            mk(1, INVOKE, "write", [[0, 2]]),
            mk(2, INVOKE, "read", [[0, None]]),
            mk(2, OK, "read", [[0, 2]]),
            mk(1, OK, "write", [[0, 2]]),
        ]
        r = wgl_tpu.check(self._model(), History(ops), capacity=64, chunk=64)
        assert r["valid"] is True

    @pytest.mark.parametrize("seed", range(6))
    def test_matches_oracle_valid(self, seed):
        from jepsen_tpu.models import MultiRegister
        from jepsen_tpu.synth import multi_register_history
        h = multi_register_history(220, keys=3, concurrency=6,
                                   crash_p=0.01, seed=seed)
        cpu = wgl_cpu.check(MultiRegister(), h)
        tpu = wgl_tpu.check(self._model(), h, capacity=256, chunk=256)
        assert cpu["valid"] == tpu["valid"] is True

    @pytest.mark.parametrize("seed", range(6))
    def test_matches_oracle_invalid(self, seed):
        from jepsen_tpu.models import MultiRegister
        from jepsen_tpu.synth import (corrupt_multi_reads,
                                      multi_register_history)
        h = corrupt_multi_reads(
            multi_register_history(220, keys=3, concurrency=6,
                                   crash_p=0.0, seed=seed),
            n=1, seed=seed)
        cpu = wgl_cpu.check(MultiRegister(), h)
        tpu = wgl_tpu.check(self._model(), h, capacity=256, chunk=256)
        assert cpu["valid"] == tpu["valid"] is False
        assert cpu["op"]["index"] == tpu["op"]["index"]

    def test_out_of_domain_value_raises(self):
        m = self._model()
        with pytest.raises(ValueError):
            m.encode_op(mk(0, INVOKE, "write", [[0, 99]]))
        with pytest.raises(ValueError):
            get_model("multi-register", keys=16, vbits=4)

    def test_string_key_rejected_not_coerced(self):
        # r5 advice regression: encode used int(k)/int(v) on raw keys,
        # so a string key "1" silently became device key 1 while the
        # host MultiRegister compares raw keys ("1" != 1) — the tiers
        # could disagree on the same history.  Non-integral keys and
        # values must refuse to encode; the facade then falls back to
        # the host oracle, which handles arbitrary keys correctly.
        m = self._model()
        with pytest.raises(ValueError, match="non-int key"):
            m.encode_op(mk(0, INVOKE, "write", [["1", 3]]))
        with pytest.raises(ValueError, match="non-int value"):
            m.encode_op(mk(0, OK, "read", [[0, "3"]]))
        # bools ARE integral (True == 1 on both tiers): still encode
        f, a, b = m.encode_op(mk(0, INVOKE, "write", [[True, 1]]))
        assert a == 0b010 and b == (1 << 4)

    def test_string_key_history_falls_back_to_host(self):
        # end to end through the competition facade: a string-keyed
        # history must produce the HOST verdict (with the fallback chain
        # annotated), not a silently-coerced device verdict
        from jepsen_tpu.checker.linearizable import Linearizable
        ops = [
            mk(0, INVOKE, "write", [["k", 1]]),
            mk(0, OK, "write", [["k", 1]]),
            mk(1, INVOKE, "read", [["k", None]]),
            mk(1, OK, "read", [["k", 1]]),
        ]
        res = Linearizable(self._model(), algorithm="tpu").check(
            None, History(ops))
        assert res["valid"] is True
        assert res.get("fallback-chain"), res


class TestTiledFullMerge:
    def test_full_merge_tiled_matches(self, monkeypatch):
        """Force the tiled full-grid merge (round-5 fix for the 65536-
        capacity compile blowup) at a tiny WIDE_SORT_ROWS and check it is
        verdict- and count-identical to the classic single-sort full merge.
        Subsumption is off so dedup is exact and the kept set (hence the
        explored count) is order-independent; the ghost burst with
        subsumption off is exactly the candidates>4C regime that executes
        the full/tiled branch."""
        from jepsen_tpu.ops import dedup
        from jepsen_tpu.synth import cas_register_history, ghost_write_burst
        h = History(ghost_write_burst(6)
                    + list(cas_register_history(60, concurrency=4,
                                                crash_p=0.0, seed=3)),
                    reindex=True)
        model = get_model("cas-register")
        monkeypatch.setattr(dedup, "SUBSUME", False)
        base = wgl_tpu.check(model, h, capacity=256, chunk=64,
                             max_capacity=4096)
        monkeypatch.setattr(dedup, "WIDE_SORT_ROWS", 8000)
        tiled = wgl_tpu.check(model, h, capacity=256, chunk=64,
                              max_capacity=4096)
        assert base["valid"] == tiled["valid"] is True, (base, tiled)
        assert base["configs-explored"] == tiled["configs-explored"]
        assert base["max-capacity-reached"] == tiled["max-capacity-reached"]

    def test_tiled_refutation_matches(self, monkeypatch):
        from jepsen_tpu.ops import dedup
        from jepsen_tpu.synth import (cas_register_history, corrupt_reads,
                                      ghost_write_burst)
        h = History(ghost_write_burst(6)
                    + list(corrupt_reads(
                        cas_register_history(60, concurrency=4, crash_p=0.0,
                                             seed=5), n=1, seed=5)),
                    reindex=True)
        model = get_model("cas-register")
        monkeypatch.setattr(dedup, "SUBSUME", False)
        base = wgl_tpu.check(model, h, capacity=256, chunk=64,
                             max_capacity=4096, explain=False)
        monkeypatch.setattr(dedup, "WIDE_SORT_ROWS", 8000)
        tiled = wgl_tpu.check(model, h, capacity=256, chunk=64,
                              max_capacity=4096, explain=False)
        assert base["valid"] == tiled["valid"] is False, (base, tiled)
        assert base["op"]["index"] == tiled["op"]["index"]

    def test_overflow_reports_explored_work(self):
        """Round-4 gap: a history that overflows before any return prunes
        must still report the in-progress frontier as explored work."""
        from jepsen_tpu.synth import bitset_ceiling_history
        model = get_model("bitset-256")
        h = bitset_ceiling_history(12, n_clean=60)
        r = wgl_tpu.check(model, h, capacity=128, chunk=64,
                          max_capacity=1024)
        assert r["valid"] == "unknown"
        assert r["configs-explored"] > 0, r
        assert r["max-capacity-reached"] == 1024, r

    def test_tiled_branch_executes_on_bitset_pileup(self, monkeypatch):
        """A shape where the full/tiled branch EXECUTES: a 9-ghost bitset
        pileup's mid-rounds burst past 4C candidates at C=512 and the
        incompressible set then overflows the fixed capacity.  Both
        engines must degrade to the same unknown verdict with nonzero
        explored work.  (On the overflow path the explored diagnostic is a
        lower bound and may differ between classic and tiled: the classic
        merge's `total` counts kept rows past capacity, folds clip
        per-fold — a conservative difference on an already-degraded
        verdict.)"""
        from jepsen_tpu.ops import dedup
        from jepsen_tpu.synth import bitset_ceiling_history
        model = get_model("bitset-256")
        h = bitset_ceiling_history(9, n_clean=40)
        base = wgl_tpu.check(model, h, capacity=512, chunk=64,
                             max_capacity=512)
        monkeypatch.setattr(dedup, "WIDE_SORT_ROWS", 4000)
        tiled = wgl_tpu.check(model, h, capacity=512, chunk=64,
                              max_capacity=512)
        assert base["valid"] == tiled["valid"] == "unknown", (base, tiled)
        assert base["configs-explored"] > 0
        assert tiled["configs-explored"] > 0


class TestEngineCacheVariant:
    def test_model_variants_do_not_collide(self):
        """Regression: compiled engines cache by (name, variant, shape);
        multi-register vbits=3 and vbits=4 share name/state_size/init, so
        without the variant key the second check silently ran the first's
        step function (caught as an order-dependent differential flake in
        the full suite)."""
        from jepsen_tpu.models import MultiRegister
        from jepsen_tpu.synth import (corrupt_multi_reads,
                                      multi_register_history)
        m3 = get_model("multi-register", keys=3, vbits=3)
        h_small = multi_register_history(60, keys=3, concurrency=4,
                                         crash_p=0.0, seed=1)
        wgl_tpu.check(m3, h_small, capacity=256, chunk=256)
        m4 = get_model("multi-register", keys=3, vbits=4)
        h = corrupt_multi_reads(
            multi_register_history(220, keys=3, concurrency=6,
                                   crash_p=0.0, seed=0), n=1, seed=0)
        cpu = wgl_cpu.check(MultiRegister(), h)
        tpu = wgl_tpu.check(m4, h, capacity=256, chunk=256)
        assert cpu["valid"] == tpu["valid"] is False
        assert cpu["op"]["index"] == tpu["op"]["index"]


class TestAutoChunk:
    def test_rule(self):
        """chunk=None routes through auto_chunk: coarse only for
        ghost-light histories on single-lane-state models (measured
        rationale in the constant's comment)."""
        from jepsen_tpu.checker.prep import prepare
        from jepsen_tpu.checker.wgl_tpu import (AUTO_CHUNK_COARSE,
                                                AUTO_CHUNK_FINE, auto_chunk)
        reg = get_model("cas-register")
        light = prepare(cas_register_history(120, concurrency=4,
                                             crash_p=0.0, seed=1), reg)
        heavy = prepare(cas_register_history(300, concurrency=4,
                                             crash_p=0.08, seed=1), reg)
        assert auto_chunk(light, reg) == AUTO_CHUNK_COARSE
        assert heavy.n_ghosts > 8
        assert auto_chunk(heavy, reg) == AUTO_CHUNK_FINE
        from jepsen_tpu.synth import multi_register_history
        mr = get_model("multi-register", keys=3, vbits=3)
        mlight = prepare(multi_register_history(80, keys=3, concurrency=4,
                                                crash_p=0.0, seed=1), mr)
        assert auto_chunk(mlight, mr) == AUTO_CHUNK_FINE  # multi-lane state

    def test_default_chunk_is_auto(self):
        h = cas_register_history(120, concurrency=4, crash_p=0.0, seed=2)
        r = wgl_tpu.check(get_model("cas-register"), h, capacity=64)
        assert r["valid"] is True
