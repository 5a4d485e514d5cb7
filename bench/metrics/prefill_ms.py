"""Engine layer: mean host time of a prefill in the traced slice, in ms:
the engine counter ``stats["prefill_s"]`` (seconds from each prefill call
until its first token is on the host, ``launch/engine.py``) over the
prefills completed (``stats["admitted"]``), between the slice's two
snapshots.  It includes waiting behind the decode dispatch already in
flight.  None for a program without the counter."""


def read(ctx, name):
    c = ctx["counters"]
    a, b = c.get("slice_start", {}), c.get("slice_end", {})
    if "prefill_s" not in a or "prefill_s" not in b:
        return None
    n = b["admitted"] - a["admitted"]
    if n <= 0:
        return None
    return 1e3 * (b["prefill_s"] - a["prefill_s"]) / n
