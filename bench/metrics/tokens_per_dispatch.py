"""Engine layer: decode tokens retired per decode dispatch in the traced
slice (engine counter ``decode_steps``; tokens from the per-token stamps).
A dispatch that fuses more micro-steps, or keeps more slots live, raises
it."""


def read(ctx, name):
    c = ctx["counters"]
    if "slice_start" not in c or "slice_end" not in c:
        return None
    steps = c["slice_end"]["decode_steps"] - c["slice_start"]["decode_steps"]
    if steps <= 0:
        return None
    return ctx["work"]["decode_tokens"] / steps
