"""Device layer: ``peak_bytes_in_use`` of the fullest chip after the
window, in GB (1e9 bytes)."""


def read(ctx, name):
    return ctx["peak_bytes"] / 1e9 if ctx["peak_bytes"] else None
