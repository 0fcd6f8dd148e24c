"""Device-mesh parallelism for the analysis engines.

Two axes, matching how the reference scales analysis (SURVEY.md §2.4, §5.7):

- ``data`` — independent sub-histories checked in parallel (the reference
  shards workloads per key via jepsen.independent and pmaps per-key checks,
  jepsen/src/jepsen/independent.clj:213-317).  Embarrassingly parallel:
  a batch of prepared histories is sharded across the mesh.
- ``model`` — ONE long history's configuration frontier sharded across
  devices (the reference's answer was "keep per-key histories short because
  the search is NP-hard", independent.clj:1-7; ours is to split the frontier).
  Closure candidates are exchanged with all_gather; every device dedups the
  global set identically and keeps its slice.
"""

from jepsen_tpu.parallel.mesh import make_mesh  # noqa: F401
from jepsen_tpu.parallel.batch import batch_stats, check_batch  # noqa: F401
from jepsen_tpu.parallel.megabatch import check_megabatch  # noqa: F401
from jepsen_tpu.parallel.sharded import check_sharded  # noqa: F401
