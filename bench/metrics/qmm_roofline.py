"""Kernel layer: the fused int8 epitome kernels' share of their roofline
in the traced slice, in %.  The least time is what the chip needs for the
work those calls did for the slice's requests (``bench/work.py``: per
call the larger of operations at the bf16 peak and bytes at HBM
bandwidth; decode micro-steps at the rows they served); the device time
is the sum of the Mosaic kernels' events in the trace.  The program's two
int8 kernels are the only Mosaic kernels on these paths; until they carry
names of their own (see PERF.md) every ``tpu_custom_call`` counts, which
can only lower the share."""


def read(ctx, name):
    spent = ctx["trace"]["kernel_s"]
    least = ctx["work"].get("kernel_least_s", 0.0)
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent
