"""Device time of the collectives inside the outer step, per sync (ms).

All-reduce, all-gather, reduce-scatter, all-to-all and collective-permute
ops that run inside the ``jit_outer_fn`` module: the outer exchange across
groups (``sync/strategies.py``). Only a cell with more than one group has
any.
"""

import devtrace

MODULE = "jit_outer_fn"


def read(ctx):
    red = ctx["trace"]
    _, syncs = red.modules(MODULE)
    secs, count = red.ops(devtrace.is_collective, MODULE)
    if not syncs or not count:
        return None
    return 1e3 * secs / syncs
