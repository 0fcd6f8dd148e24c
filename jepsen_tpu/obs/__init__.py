"""Observability: distributed tracing, histograms, and a flight recorder.

The telescope the serving tier looks through.  Three instruments, all
cheap enough to leave on in production and all exportable through the
existing ``/metrics`` surface:

- ``trace``    — trace-context ids minted at ``Request`` submit and
                 propagated on every wire frame, plus the Chrome
                 trace-event (Perfetto) conversion for merged traces.
- ``hist``     — log-bucketed latency/compile-time histograms, their
                 buckets powers of two like the engine's shape rungs;
                 and ``first_use_stats()``, what the process paid once
                 (JAX's trace, lower and load seconds by engine shape
                 and by eager program, the first ``core.analyze``
                 against the later ones).
- ``recorder`` — a bounded process-wide ring of structured events with
                 an atomic Chrome-trace export (``RECORDER``), and
                 ``span``/``instant``: the checker path's layer
                 boundaries, for the ring and for ``jax.profiler``.

Import discipline: a leaf.  The engine and the service both import
this package, so nothing here imports ``jepsen_tpu.engine``,
``jepsen_tpu.serve`` or anything else of the checker path, at module
scope or inside a function (``tests/test_layering.py``).
"""

from jepsen_tpu.obs.hist import (  # noqa: F401
    Histogram, HistogramSet, compile_hist_stats, first_use_stats,
    merge_hist_snapshots, observe_compile, timed_first_call,
)
from jepsen_tpu.obs.recorder import (  # noqa: F401
    RECORDER, FlightRecorder, instant, span,
)
from jepsen_tpu.obs.trace import (  # noqa: F401
    chrome_document, chrome_events_from_trace, new_span_id, new_trace_id,
    wall_anchor, write_chrome,
)

__all__ = [
    "Histogram", "HistogramSet", "compile_hist_stats", "first_use_stats",
    "merge_hist_snapshots", "observe_compile", "timed_first_call",
    "RECORDER", "FlightRecorder", "instant", "span",
    "chrome_document", "chrome_events_from_trace", "new_span_id",
    "new_trace_id", "wall_anchor", "write_chrome",
]
