"""The chip: refuse to start without it, stamp it, read its memory; and
JAX's own compile events (a copy of ``chip_smoke.CompileWatch``)."""

from __future__ import annotations

from typing import Any, Dict


class NoChip(SystemExit):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int) -> Dict[str, Any]:
    """The device stamp, as JAX reports it; exits non-zero with no result
    line on any platform but ``tpu`` or with fewer than ``chips`` chips.
    Sets no platform: JAX decides."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"benchmark: no TPU: jax.devices()[0] is "
                     f"{d.platform!r} ({d.device_kind}); nothing measured")
    if len(devs) < chips:
        raise NoChip(f"benchmark: the cell asks for {chips} chip(s) and "
                     f"JAX finds {len(devs)}; nothing measured")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_stats(chips: int) -> Dict[str, Any]:
    """``memory_stats()`` of the fullest of the chips used."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    return max(stats, key=lambda s: s.get("peak_bytes_in_use", 0))


class CompileWatch:
    """Counts JAX's compile and persistent-cache events between marks:
    how many programs went to the backend compiler and for how long, and
    how many came off the disk cache or missed it."""

    _BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self) -> None:
        import jax.monitoring as mon
        self.now = self._zero()
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    @staticmethod
    def _zero() -> Dict[str, float]:
        return {"backend_compiles": 0, "backend_compile_s": 0.0,
                "cache_hits": 0, "cache_misses": 0}

    def _on_event(self, name: str, **_: Any) -> None:
        if name == self._HIT:
            self.now["cache_hits"] += 1
        elif name == self._MISS:
            self.now["cache_misses"] += 1

    def _on_duration(self, name: str, secs: float, **_: Any) -> None:
        if name == self._BACKEND_COMPILE:
            self.now["backend_compiles"] += 1
            self.now["backend_compile_s"] += secs

    def mark(self) -> Dict[str, float]:
        """The counts since the last mark; starts counting anew."""
        done, self.now = self.now, self._zero()
        return done
