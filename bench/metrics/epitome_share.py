"""Layer dispatch: device self-time of the ops under the
``epim.epitome_matmul`` scope (``core/layers._dispatch_epitome_matmul``:
fold, int8 kernels and their pads and casts) over device busy time in the
traced slice, in %, from one op_name-path reduction of the trace
(``bench/spans.py``).  None for a program without the scope."""
from bench import spans


def read(ctx, name):
    s = ctx["slice"]
    return spans.scope_share(s.dir, "epim.epitome_matmul") if s.traced else None
