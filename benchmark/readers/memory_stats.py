"""One key of ``device.memory_stats()`` on the fullest chip, read after the
window."""


def read(ctx, key):
    return ctx["memory"].get(key)
