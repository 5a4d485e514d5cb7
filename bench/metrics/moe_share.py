"""MoE layer: device self-time of the ops under the ``epim.moe`` scope
(``models/moe.moe_held``: routing, the held experts' int8 kernels, their
folds and the combine) over device busy time in the traced slice, in %,
from the op_name-path reduction of ``bench/spans.py``.  None for a
program without the scope."""
from bench import spans


def read(ctx, name):
    s = ctx["slice"]
    return spans.scope_share(s.dir, "epim.moe") if s.traced else None
