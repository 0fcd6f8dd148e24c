"""engine: the shared device-engine substrate.

Every device checker in this repo — the single-history wgl engine
(checker/wgl_tpu.py), the vmapped batch driver (parallel/batch.py), the
elle cycle engine (elle_tpu/engine.py), the monitor's epoch checkers
(monitor/epochs.py) — answers the same five questions: what shape do I
compile for, where do compiled engines live, how long may I run, what
happens when the device fails, and what evidence must a refutation
carry.  This package owns the one answer to each:

- ``ladder``   — the pow2 bucket/shape ladder: the rungs and the
  shape/chunk/window/capacity math derived from them;
- ``cache``    — the bounded LRU compiled-engine cache and its shared
  process-wide instance;
- ``groups``   — lane grouping under the 512-lane vmap cap;
- ``budget``   — Deadline plumbing; exhaustion degrades to ``unknown``;
- ``fallback`` — the tpu->cpu chain; a device error never decides a
  verdict;
- ``witness``  — refutation discipline: device lanes flag, the CPU
  recovers the witness — never a fabricated ``valid: False``;
- ``plugins``  — the drop-in seam: new consistency models register as
  (device kernel, checker name) pairs over the unchanged engine;
  ``opacity`` and ``model_plugin`` are its first consumers.

See docs/engines.md for the contract and the write-a-plugin walkthrough.
"""

from jepsen_tpu.engine.budget import Deadline, exhausted_result  # noqa: F401
from jepsen_tpu.engine.cache import (  # noqa: F401
    CACHE, EngineCache, engine_cache_stats,
)
from jepsen_tpu.engine.fallback import (  # noqa: F401
    annotate_fallback, chain_entry, warn_fallback,
)
from jepsen_tpu.engine.groups import (  # noqa: F401
    MAX_LANES_PER_GROUP, bounded_group_cap, group_slices,
)
from jepsen_tpu.engine.ladder import (  # noqa: F401
    LANE_EVENTS_PER_DISPATCH, batch_chunk, batch_shape, next_capacity,
    round_window,
)
from jepsen_tpu.engine.plugins import (  # noqa: F401
    register_builtin_plugins, register_model_plugin, registered_plugins,
)
from jepsen_tpu.engine.witness import (  # noqa: F401
    WITNESS_BUDGET, cpu_witness, refuted_result,
)
