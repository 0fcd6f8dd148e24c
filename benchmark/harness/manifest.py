"""Find a cell's files by the names in ``BENCHMARK.json``.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name: a later PR adds ``configs/<config>.json``, ``traffic/<traffic>.json``
or ``layers/<metric>.json`` and an entry in the manifest, and edits no file
that is there.
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class ManifestError(Exception):
    """The manifest or one of the files it names is missing or wrong."""


def _load(path: str) -> Dict[str, Any]:
    if not os.path.isfile(path):
        raise ManifestError(f"missing file: {os.path.relpath(path, ROOT)}")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def manifest() -> Dict[str, Any]:
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


def by_name(entries: List[Dict[str, Any]], name: str,
            what: str) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    raise ManifestError(f"no {what} named {name!r} in BENCHMARK.json")


class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    def __init__(self, name: str, man: Dict[str, Any] | None = None) -> None:
        self.manifest = man if man is not None else manifest()
        self.entry = by_name(self.manifest["workloads"], name, "workload")
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg = by_name(self.manifest["configs"], self.entry["config"],
                      "config")
        self.config = _load(os.path.join(ROOT, cfg["file"]))
        self.traffic = _load(os.path.join(
            BENCH_DIR, "traffic", self.entry["traffic"] + ".json"))

    def _metrics(self, group: str) -> List[Dict[str, Any]]:
        return [m for m in self.manifest[group]
                if self.name in m.get("workloads", [self.name])]

    def end_to_end(self) -> List[Dict[str, Any]]:
        return self._metrics("end_to_end")

    def per_layer(self) -> List[Dict[str, Any]]:
        """This cell's per-layer metrics, each with its reader file's
        ``reader`` and ``args`` merged in."""
        out = []
        for m in self._metrics("per_layer"):
            spec = _load(os.path.join(BENCH_DIR, "layers",
                                      m["name"] + ".json"))
            out.append({**m, "reader": spec["reader"],
                        "args": spec.get("args", {})})
        return out


def plugin(package: str, name: str, attr: str) -> Callable[..., Any]:
    """``benchmark/<package>/<name>.py``'s ``attr``: how a loop, a reader
    or a reference is found by the name a data file gives."""
    if not name.replace("_", "").isalnum():
        raise ManifestError(f"bad {package} name {name!r}")
    try:
        mod = importlib.import_module(f"{package}.{name}")
    except ModuleNotFoundError as e:
        raise ManifestError(
            f"no benchmark/{package.replace('.', '/')}/{name}.py") from e
    return getattr(mod, attr)


def peaks(device_kind: str) -> Dict[str, Any]:
    """The published peaks of this device; an unknown kind is an error."""
    table = _load(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if device_kind not in table:
        raise ManifestError(f"device kind {device_kind!r} is not in "
                            "benchmark/peaks.json")
    return table[device_kind]
