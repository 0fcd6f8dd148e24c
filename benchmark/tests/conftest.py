"""A traffic file may name the module its generator lives in
(``generator_module``, the ``offline_plug`` loop): the tests that look a
generator up in ``gen.histories.GENERATORS`` find it there as a run does."""

import glob
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(BENCH), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness.loops import offline_plug  # noqa: E402

for path in sorted(glob.glob(os.path.join(BENCH, "traffic", "*.json"))):
    with open(path, encoding="utf-8") as f:
        traffic = json.load(f)
    if "generator_module" in traffic:
        offline_plug.register(traffic)
