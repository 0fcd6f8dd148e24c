"""SHAPE01: engine-entry shapes in serve/ derive from the bucket ladder.

The serving layer's whole compile-cache story rests on one discipline:
every shape that reaches a device engine (pad targets, window floors,
chunk sizes) comes from ``engine/ladder.py``'s power-of-two ladder, so
the set of compiled signatures is bounded by the ladder, not by the
traffic.  One call site that pads to a raw history length (``len(h)``,
``max(p.window ...)``) silently reopens an unbounded compile cache —
every novel history size compiles a fresh executable and the service
death-spirals under diverse load.

The rule audits engine entry points called from serve/ (``check_batch``,
``check_megabatch``, ``make_engine``, ``events_array``, ``pack_group``):

- shape-carrying kwargs (``window_floor``, ``n_pad_floor``, ``chunk``,
  ``n_pad``, ``b_pad``, ``window``, ``pad_to``), when present, must be
  *bucket-derived*: reference a ``*bucket*``/``*floor*``/``pow2`` name,
  a ``ladder.`` bucket helper, or the canonical ``batch_chunk`` derivation
  (literal ``0`` = "disabled" is also fine).  Non-zero literals and raw
  shape expressions fire;
- a ``check_batch`` call *missing* its floor kwarg fires — the default
  floor of 0 means "pad to this history's own size", exactly the
  unbounded behaviour — except when the call pins ``engine="cpu"``
  (the host tier compiles nothing);
- a ``check_megabatch`` call must pass BOTH ``window_floor`` and
  ``ev_floor`` (the megabatch packer buckets internally, but without
  the cell's floors successive dispatches of one bucket land in
  different internal rungs and the lane/shape ladder decoheres), and
  its ``lanes`` count, when present, must come from the lane ladder
  (``mega_lane_bucket``) like every other shape.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterator, List, Optional

from jepsen_tpu.lint.findings import Finding
from jepsen_tpu.lint.rules import dotted, qualname_of, walk_with_parents

RULE = "SHAPE01"

SCOPE = ("jepsen_tpu/serve/", "jepsen_tpu/engine/")

#: kwargs that carry a shape into an engine, per entry-point name.
_SHAPE_KWARGS = {
    "check_batch": ("window_floor", "n_pad_floor", "chunk", "pad_to"),
    "check_megabatch": ("window_floor", "ev_floor", "lanes", "chunk"),
    "make_engine": ("window", "capacity", "gwords"),
    "events_array": ("chunk", "pad_to"),
    "pack_group": ("n_pad", "b_pad"),
    # engine-substrate entry points: the shared shape derivation itself
    # (ladder.batch_shape) and the model factories whose kwargs become
    # engine-cache key components (a raw len(h) here is exactly the
    # unbounded-compile-cache leak the ladder exists to close).
    "batch_shape": ("window_floor",),
    "fifo_queue_jax": ("slots",),
    "txn_register_jax": ("keys", "vbits"),
    "multi_register_jax": ("keys", "vbits"),
    "bitset_jax": ("domain",),
    # the state-width ladder derivations: their state_width argument is
    # an engine-cache key component (quantized internally, but a call
    # site threading a raw shape through a kwarg still gets audited)
    "mega_chunk": ("state_width",),
    "state_capacity": ("state_width",),
}

#: which floor kwarg a check_batch variant requires, by defining module.
_FLOOR_FOR_ORIGIN = {
    "jepsen_tpu.parallel.batch": "window_floor",
    "jepsen_tpu.elle_tpu.engine": "n_pad_floor",
}

#: floors a check_megabatch call must ALL pass (the packer buckets
#: internally, but the cell's floors are what pin successive dispatches
#: of one bucket to one internal rung).
_MEGABATCH_FLOORS = ("window_floor", "ev_floor")

_BUCKETISH_NAME = re.compile(r"bucket|floor|pow2", re.IGNORECASE)
_BUCKETISH_FUNC = re.compile(
    r"bucket|floor|pow2|batch_chunk|mega_chunk|capacity")


def _bucket_derived(node: ast.AST) -> bool:
    """Is this shape expression anchored in the ladder?  True when any
    name/call in it smells of the bucket derivation; literal 0 (feature
    disabled) also passes."""
    if isinstance(node, ast.Constant):
        return node.value == 0 or node.value is None
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and _BUCKETISH_NAME.search(sub.id):
            return True
        if isinstance(sub, ast.Attribute) \
                and _BUCKETISH_NAME.search(sub.attr):
            return True
        if isinstance(sub, ast.Call) \
                and _BUCKETISH_FUNC.search(dotted(sub.func)):
            return True
    return False


def _engine_is_cpu(call: ast.Call) -> bool:
    for kw in call.keywords:
        if kw.arg == "engine" and isinstance(kw.value, ast.Constant) \
                and kw.value.value == "cpu":
            return True
    return False


def _import_origins(tree: ast.Module) -> Dict[ast.AST, Dict[str, str]]:
    """Per-scope ``from X import name [as alias]`` bindings: scope node ->
    {local name: defining module}.  Scopes are the module and each
    function def; lookup walks outward."""
    list(walk_with_parents(tree))
    origins: Dict[ast.AST, Dict[str, str]] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.ImportFrom) or node.module is None:
            continue
        scope: ast.AST = node
        while not isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.Module)):
            scope = scope.parent  # type: ignore[attr-defined]
        table = origins.setdefault(scope, {})
        for alias in node.names:
            table[alias.asname or alias.name] = node.module
    return origins


def _origin_of(call: ast.Call, origins: Dict[ast.AST, Dict[str, str]],
               name: str) -> Optional[str]:
    cur = getattr(call, "parent", None)
    while cur is not None:
        table = origins.get(cur)
        if table and name in table:
            return table[name]
        cur = getattr(cur, "parent", None)
    return None


def check(tree: ast.Module, src_lines: List[str],
          path: str) -> Iterator[Finding]:
    origins = _import_origins(tree)          # also annotates parents
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fname = dotted(node.func).split(".")[-1]
        if fname not in _SHAPE_KWARGS:
            continue
        qn = qualname_of(node)
        kwargs = {kw.arg: kw.value for kw in node.keywords if kw.arg}
        for kw_name in _SHAPE_KWARGS[fname]:
            value = kwargs.get(kw_name)
            if value is not None and not _bucket_derived(value):
                yield Finding(
                    RULE, path, value.lineno,
                    f"`{fname}(..., {kw_name}=...)` in {qn} passes a "
                    f"shape not derived from the bucket ladder",
                    hint="derive it via engine/ladder.py (events_bucket/"
                         "width_bucket/elle_bucket/...) so the compile "
                         "cache stays bounded by the ladder")
        if fname == "check_megabatch" and not _engine_is_cpu(node):
            for r in _MEGABATCH_FLOORS:
                if r not in kwargs:
                    yield Finding(
                        RULE, path, node.lineno,
                        f"`check_megabatch(...)` in {qn} omits `{r}`: "
                        f"without the cell's floor, successive dispatches "
                        f"of one bucket land in different internal packer "
                        f"rungs and the shape ladder decoheres",
                        hint="pass the cell's bucket as the floor (see "
                             "scheduler._dispatch_wgl's megabatch arm)")
        if fname == "check_batch" and not _engine_is_cpu(node):
            origin = _origin_of(node, origins, dotted(node.func)
                                .split(".")[0] or fname)
            floor = _FLOOR_FOR_ORIGIN.get(origin or "")
            required = (floor,) if floor else tuple(_FLOOR_FOR_ORIGIN
                                                    .values())
            if not any(r in kwargs for r in required):
                want = " or ".join(f"`{r}`" for r in required)
                yield Finding(
                    RULE, path, node.lineno,
                    f"`check_batch(...)` in {qn} omits {want}: the "
                    f"default floor pads each batch to its own raw "
                    f"shape, reopening an unbounded compile cache",
                    hint="pass the bucket as the floor (see scheduler."
                         "_dispatch_*), or pin engine=\"cpu\" for a "
                         "host-tier call that compiles nothing")
