"""Engine layer: device-idle time on the first chip in the traced slice
that lies under an open ``epim.*`` host span of the engine
(``launch/engine.py``: step, submit, admit, prefill, activate, retire,
dispatch), over the slice's length, in %.  The rest of the idle share is
host time outside the engine (the benchmark's own loop).  None for a
program without the spans (``bench/spans.py``)."""
from bench import spans


def read(ctx, name):
    s = ctx["slice"]
    if not s.traced:
        return None
    got = spans.idle(s.dir)
    return None if got is None else 100.0 * got["epim_s"] / s.window_s
