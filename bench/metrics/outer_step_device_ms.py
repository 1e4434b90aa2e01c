"""Device time of one run of the outer-step program, per sync (ms).

The jitted eager outer step (``parallel/steps.py``, ``jit(outer_fn)``)
shows in the trace as an XLA module named ``jit_outer_fn``.
"""

MODULE = "jit_outer_fn"


def read(ctx):
    secs, count = ctx["trace"].modules(MODULE)
    return 1e3 * secs / count if count else None
