"""Persistent XLA compilation cache: one directory, placeable from outside.

An engine shape compiles in seconds to tens of seconds and a 10k-op check
climbs a ladder of them, so a fresh process pays more in compiles than in
checking.  JAX ships a persistent cache (serialized executables keyed by
HLO + compile options + platform); enabling it makes the second process's
"compile" a disk load.

The reference has no counterpart (knossos is a JVM library, warmed by the
JIT per-process); this is a TPU-native concern.

One rule decides the directory.  With ``JAX_COMPILATION_CACHE_DIR`` set,
JAX already points at it and this module sets no directory.  Otherwise
the cache lives at :data:`CACHE_DIR`, a fixed path inside the checkout
(under the gitignored ``store/``) that does not depend on the working
directory, a test's ``--store``, the pid or the time: a directory that
moves never hits again.
"""

from __future__ import annotations

import os

#: ``<checkout>/store/cache/xla``, from this file's own location.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    "store", "cache", "xla")


def init_compilation_cache() -> str:
    """Turn JAX's persistent compilation cache on for this process: the
    one shared init of every engine build, the serve service, each bench
    tier and the CLI.  Idempotent; safe to call before or after the first
    trace.  Also where the program starts to count what JAX builds
    (``obs.hist.first_use_stats()``).  Never raises (a read-only
    filesystem or a broken JAX install must not take checking down with
    it); returns the directory in force, or "" when caching stayed
    off."""
    try:
        import jax

        from jepsen_tpu.obs.hist import listen_first_use
        listen_first_use()
        if jax.default_backend() == "cpu" \
                and "JEPSEN_TPU_CACHE_CPU" not in os.environ:
            # CPU AOT cache entries embed exact machine features and XLA
            # warns they may SIGILL on a host whose feature set differs
            # (virtual-mesh test runs move between machines); CPU compiles
            # are cheap, so only accelerator executables are worth
            # persisting.
            return ""
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR") \
                and jax.config.jax_compilation_cache_dir != CACHE_DIR:
            os.makedirs(CACHE_DIR, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        # Cache everything: engine shapes compile in 1-40 s each, and even
        # sub-second helper kernels add up across the escalation ladder.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        return jax.config.jax_compilation_cache_dir or ""
    except Exception:  # noqa: BLE001
        return ""
