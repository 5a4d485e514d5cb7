"""Layer dispatch: device self-time of the ops under the ``epim.fold``
scope (``kernels/ops.fold_rows``, the segment sum of the activations into
epitome rows) over device busy time in the traced slice, in %, from the
same reduction as ``epitome_share``.  None for a program without the
scope, or one that folds inside the kernel."""
from bench import spans


def read(ctx, name):
    s = ctx["slice"]
    return spans.scope_share(s.dir, "epim.fold") if s.traced else None
