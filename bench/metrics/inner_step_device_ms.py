"""Device time of one run of the inner-step program (ms).

The jitted inner step (``parallel/steps.py``, ``jit(stepfn)``) shows in
the trace as an XLA module named ``jit_stepfn``; its runs' durations are
averaged over runs and chips.
"""

MODULE = "jit_stepfn"


def read(ctx):
    secs, count = ctx["trace"].modules(MODULE)
    return 1e3 * secs / count if count else None
