"""Mamba layer: device self-time of the ops under the ``epim.mamba`` scope
(``models/ssm.mamba_mix``: the conv, x_proj and dt_proj, the selective
scan and the gate) over device busy time in the traced slice, in %, from
the op_name-path reduction of ``bench/spans.py``.  None for a program
without the scope."""
from bench import spans


def read(ctx, name):
    s = ctx["slice"]
    return spans.scope_share(s.dir, "epim.mamba") if s.traced else None
