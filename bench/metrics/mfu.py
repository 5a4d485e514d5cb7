"""Model layer: useful model operations completed in the traced slice
over the slice's time at the chip's bf16 peak, in %.  Operations are
counted from shapes by ``bench/work.py`` (epitome layers at 2 T m N), so
the number is the same whatever implements the model."""


def read(ctx, name):
    s = ctx["slice"]
    ops = ctx["work"].get("useful_ops", 0)
    if not s.traced or ops <= 0:
        return None
    return 100.0 * ops / (s.window_s * ctx["peaks"]["bf16_flops"])
