"""The trace tier: lint the engines' *jaxprs*, not their source.

The AST tier proves properties of the code we wrote; this tier proves
properties of what XLA will actually compile.  Two checks:

- **TRACE01 — no host round-trips in the compiled body.**  Each device
  engine is traced with :func:`jax.make_jaxpr` over representative
  bucket shapes and the resulting jaxpr (recursively, through
  pjit/scan/cond sub-jaxprs) must contain no callback or infeed/outfeed
  primitive.  A ``pure_callback`` smuggled into an engine by a future
  refactor survives jit — it just makes every dispatch block on the
  host — so source review alone cannot guarantee its absence.

- **TRACE02 — the compiled-signature universe equals the bucket
  ladder.**  For a synthetic spread of workload shapes (events, widths,
  lane counts) the derived engine entry signature (window, capacity,
  chunk, lane pad) must collapse to exactly the bucket ladder's image:
  ``|signatures| <= |buckets|``.  A raw shape leaking into any
  signature component makes the signature set grow with the sample set,
  which is precisely the unbounded-compile-cache failure SHAPE01 guards
  at the call-site level — this check proves it end-to-end through the
  real derivation functions.

Tracing is backend-independent (``make_jaxpr`` never compiles), so the
tier runs fine under ``JAX_PLATFORMS=cpu`` in CI.
"""

from __future__ import annotations

from typing import Any, Callable, Iterable, Iterator, List, Sequence, Tuple

from jepsen_tpu.lint.findings import Finding

RULE_CALLBACK = "TRACE01"
RULE_LADDER = "TRACE02"

#: primitives that force a device<->host transition inside compiled code.
BANNED_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "infeed", "outfeed",
})

#: synthetic workload spread: (n_events, width/concurrency, lanes).
#: Deliberately off-bucket values — the point is that messy real-world
#: shapes collapse onto the ladder.
DEFAULT_SAMPLES: Tuple[Tuple[int, int, int], ...] = (
    (5, 1, 1), (63, 2, 2), (64, 2, 3), (65, 3, 4), (100, 5, 7),
    (128, 8, 8), (129, 9, 17), (300, 11, 64), (511, 16, 100),
    (1000, 24, 200), (4097, 33, 513),
)


# -- jaxpr walking -----------------------------------------------------------

def _sub_jaxprs(value: Any) -> Iterator[Any]:
    """Jaxprs nested inside one eqn-params value (ClosedJaxpr, Jaxpr, or
    lists/tuples of either)."""
    if hasattr(value, "jaxpr"):               # ClosedJaxpr
        value = value.jaxpr
    if hasattr(value, "eqns"):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)


def iter_eqns(jaxpr: Any) -> Iterator[Any]:
    """Every equation in ``jaxpr``, recursing through sub-jaxprs (pjit
    bodies, scan/while/cond branches)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_eqns(sub)


def check_jaxpr_clean(fn: Callable, args: Sequence[Any], label: str,
                      path: str = "<trace>") -> List[Finding]:
    """Trace ``fn(*args)`` and report every banned primitive in the
    resulting jaxpr.  A trace *failure* is itself a finding: an engine
    that no longer traces cannot ship."""
    import jax
    try:
        closed = jax.make_jaxpr(fn)(*args)
    except Exception as e:  # noqa: BLE001 — any trace error is a finding
        return [Finding(
            RULE_CALLBACK, path, 0,
            f"engine '{label}' failed to trace: {type(e).__name__}: {e}",
            hint="the engine must stay traceable with make_jaxpr; see "
                 "docs/static_analysis.md#trace-tier")]
    out = []
    for eqn in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name in BANNED_PRIMITIVES:
            out.append(Finding(
                RULE_CALLBACK, path, 0,
                f"banned primitive '{name}' in traced engine '{label}': "
                f"a host round-trip inside compiled code",
                hint="engines must be pure device code; hoist the host "
                     "interaction into the chunk driver"))
    return out


# -- the engines we trace ----------------------------------------------------

def trace_engine_findings() -> List[Finding]:
    """Trace the real device engines over representative bucket shapes."""
    import jax.numpy as jnp

    from jepsen_tpu.checker.wgl_tpu import make_engine
    from jepsen_tpu.elle_tpu.closure import lane_flags_fn
    from jepsen_tpu.models import get_model

    findings: List[Finding] = []
    model = get_model("cas-register")

    for single_round in (False, True):
        carry0, _, run_chunk = make_engine(
            model, window=8, capacity=64, gwords=1,
            single_round_closure=single_round)
        label = ("wgl-batch[single-round]" if single_round
                 else "wgl[multi-round]")
        events = jnp.zeros((64, 10), jnp.int32)
        findings.extend(check_jaxpr_clean(
            run_chunk, (carry0(), events), label,
            path="jepsen_tpu/checker/wgl_tpu.py"))

    for n_pad, realtime in ((32, False), (32, True), (64, False)):
        fn = lane_flags_fn(n_pad, realtime)
        b, e = 2, 64
        args = (jnp.zeros((b, 3, e), jnp.int32),
                jnp.zeros((b, 3, e), jnp.int32),
                jnp.zeros((b, n_pad), jnp.int32),
                jnp.zeros((b, n_pad), jnp.int32))
        findings.extend(check_jaxpr_clean(
            fn, args, f"elle-lane[n={n_pad},rt={realtime}]",
            path="jepsen_tpu/elle_tpu/closure.py"))

    # The engine-plugin kernels (queue/set/txn-register) ride the same
    # make_engine body, but their step/encode closures are new device
    # code: trace each through the engine so a host round-trip in a
    # kernel is caught exactly like one in the engine itself.
    for name, kw in (("fifo-queue", {"slots": 8}), ("set", {}),
                     ("txn-register", {})):
        m = get_model(name, **kw)
        carry0, _, run_chunk = make_engine(m, window=8, capacity=64,
                                           gwords=1)
        events = jnp.zeros((64, 10), jnp.int32)
        findings.extend(check_jaxpr_clean(
            run_chunk, (carry0(), events), f"wgl[{name}]",
            path="jepsen_tpu/models/collections.py"))
    return findings


# -- ladder/signature stability ----------------------------------------------

def signature_stability_findings(
        samples: Iterable[Any],
        derive_signature: Callable[[Any], Tuple],
        derive_bucket: Callable[[Any], Tuple],
        label: str, path: str = "<ladder>") -> List[Finding]:
    """|signatures over samples| must not exceed |buckets over samples|:
    every signature component is a pure function of the bucket, so a
    larger signature set means a raw shape leaked into the derivation."""
    samples = list(samples)
    sigs = {derive_signature(s) for s in samples}
    buckets = {derive_bucket(s) for s in samples}
    if len(sigs) > len(buckets):
        return [Finding(
            RULE_LADDER, path, 0,
            f"{label}: {len(sigs)} distinct compiled signatures from "
            f"{len(buckets)} buckets over {len(samples)} sample shapes "
            f"— a raw shape is leaking into the engine signature",
            hint="every signature component must be derived from the "
                 "bucket (engine/ladder.py), never from the history")]
    return []


def ladder_findings(samples: Sequence[Tuple[int, int, int]] =
                    DEFAULT_SAMPLES) -> List[Finding]:
    """Check the real serve-path derivations against the ladder."""
    from jepsen_tpu.checker.wgl_tpu import _round_window
    from jepsen_tpu.engine import ladder

    findings = []

    def wgl_bucket(s):
        e, w, l = s
        # the numeric ladder under events_bucket/width_bucket
        return (ladder.pow2_at_least(e, ladder.MIN_EVENTS_BUCKET),
                ladder.pow2_at_least(w, ladder.MIN_WIDTH_BUCKET),
                ladder.lane_bucket(l))

    def wgl_signature(s):
        eb, wb, lb = wgl_bucket(s)
        # exactly what scheduler._dispatch_wgl hands the batch engine
        # (register family: state width 1, the ladder's base rung)
        return (_round_window(wb), ladder.wgl_start_capacity(eb, wb),
                ladder.mega_chunk(lb, eb, 1), lb)

    findings.extend(signature_stability_findings(
        samples, wgl_signature, wgl_bucket, "wgl serve path",
        path="jepsen_tpu/serve/scheduler.py"))

    def elle_bucket(s):
        return (ladder.pow2_at_least(max(1, s[0]), ladder.MIN_N_BUCKET),)

    def elle_signature(s):
        n = s[0]
        # graphs.pack_group pads txn count to max(raw 32-multiple, floor);
        # the bucket floor must dominate or the signature tracks raw n.
        raw = max(32, -(-n // 32) * 32)
        return (max(raw, elle_bucket(s)[0]),)

    findings.extend(signature_stability_findings(
        samples, elle_signature, elle_bucket, "elle serve path",
        path="jepsen_tpu/serve/scheduler.py"))

    # The queue plugin's per-history model sizing is an engine-cache key
    # component (JaxModel.variant): run the REAL derivation over synthetic
    # enqueue streams and require it to collapse onto the pow2 ladder.
    from jepsen_tpu.engine.model_plugin import derive_queue_slots
    from jepsen_tpu.history import History, Op

    def _enq_history(n: int) -> History:
        ops = []
        for i in range(n):
            ops.append(Op(process=0, type="invoke", f="enqueue",
                          value=i, index=2 * i))
            ops.append(Op(process=0, type="ok", f="enqueue",
                          value=i, index=2 * i + 1))
        return History(ops)

    def queue_bucket(s):
        return (ladder.pow2_at_least(max(1, s[0]), 8),)

    def queue_signature(s):
        return (derive_queue_slots(_enq_history(s[0]), {})["slots"],)

    findings.extend(signature_stability_findings(
        samples, queue_signature, queue_bucket, "queue plugin slots",
        path="jepsen_tpu/engine/model_plugin.py"))

    # The megabatch state-width ladder: a plugin model's packed state
    # width (queue ring = 2 + derived slots here — the widest, messiest
    # real derivation) feeds the chunk and start-capacity components of
    # the "megav" engine-cache key.  Run the REAL ladder derivations
    # over the raw widths and require the signature to collapse onto
    # the (events, window, lanes, state-width) bucket tuple — a raw
    # ring width leaking into chunk or capacity recompiles per queue
    # size.
    def _queue_state_width(s) -> int:
        return 2 + derive_queue_slots(_enq_history(max(1, s[1])), {})["slots"]

    def state_bucket(s):
        e, w, l = s
        return (ladder.pow2_at_least(e, ladder.MIN_EVENTS_BUCKET),
                ladder.pow2_at_least(max(8, w), ladder.MIN_WIDTH_BUCKET),
                ladder.mega_lane_bucket(l),
                ladder.state_width_bucket(_queue_state_width(s)))

    def state_signature(s):
        eb, wb, lb, _ = state_bucket(s)
        raw_width = _queue_state_width(s)
        return (ladder.mega_chunk(lb, eb, raw_width),
                ladder.state_capacity(eb, wb, raw_width),
                ladder.state_width_bucket(raw_width))

    findings.extend(signature_stability_findings(
        samples, state_signature, state_bucket, "megabatch state-width",
        path="jepsen_tpu/parallel/megabatch.py"))

    # The fission sub-dispatch floors (batch window_floor / megabatch
    # ev_floor, plus the lane bucket) are engine-cache key components
    # for every post-split dispatch: run the REAL floor derivation over
    # synthetic sub-problem swarms of messy raw shapes and require the
    # resulting (window, events, lanes) triple to collapse onto the
    # ladder — a raw sub-history shape leaking into a floor recompiles
    # per split.
    from jepsen_tpu.engine.fission import subproblem_floors

    def _sub_history(n_events: int, width: int) -> History:
        w = max(1, width)
        ops = [Op(process=p, type="invoke", f="enqueue", value=p,
                  index=p) for p in range(w)]
        ops += [Op(process=p, type="ok", f="enqueue", value=p,
                   index=w + p) for p in range(w)]
        i = len(ops)
        while len(ops) < n_events:
            ops.append(Op(process=0,
                          type="invoke" if i % 2 == 0 else "ok",
                          f="enqueue", value=i, index=i))
            i += 1
        return History(ops)

    def fission_bucket(s):
        e, w, l = s
        return (ladder.pow2_at_least(max(1, e), ladder.MIN_EVENTS_BUCKET),
                ladder.pow2_at_least(max(1, w), ladder.MIN_WIDTH_BUCKET),
                ladder.mega_lane_bucket(l))

    def fission_signature(s):
        e, w, l = s
        subs = [_sub_history(e, w)] * min(3, max(1, l))
        return subproblem_floors(subs)[::-1] + (ladder.mega_lane_bucket(l),)

    findings.extend(signature_stability_findings(
        samples, fission_signature, fission_bucket, "fission sub-dispatch",
        path="jepsen_tpu/engine/fission.py"))

    # The streaming monitor's epoch dispatch (engine/stream.py): the
    # (window, capacity, epoch-events) rung triple is the shape cut of
    # the "streamv" engine-cache key.  Run the REAL rung derivation over
    # raw (new-op count, concurrency) samples and require it to collapse
    # onto the (width-bucket, epoch-events-bucket) image — a raw
    # per-epoch op count leaking into the chunk shape recompiles every
    # epoch, which is exactly the steady-state-zero-recompiles property
    # the stream smoke asserts end-to-end.
    from jepsen_tpu.engine.stream import stream_engine_rungs

    def stream_bucket(s):
        e, w, _ = s
        return (ladder.pow2_at_least(max(1, w), ladder.MIN_WIDTH_BUCKET),
                ladder.epoch_events_bucket(e))

    def stream_signature(s):
        e, w, _ = s
        return stream_engine_rungs(w, e)

    findings.extend(signature_stability_findings(
        samples, stream_signature, stream_bucket, "stream epoch dispatch",
        path="jepsen_tpu/engine/stream.py"))
    return findings


def run_trace_tier(trace_device: bool = True) -> List[Finding]:
    findings = ladder_findings()
    if trace_device:
        findings.extend(trace_engine_findings())
    return findings
