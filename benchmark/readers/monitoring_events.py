"""JAX's own monitoring events, counted by the harness's ``CompileWatch``
in one phase of the run (``setup`` or ``window``): ``backend_compiles``,
``backend_compile_s``, ``cache_hits``, ``cache_misses``."""


def read(ctx, phase, event):
    return ctx["events"].get(phase, {}).get(event)
