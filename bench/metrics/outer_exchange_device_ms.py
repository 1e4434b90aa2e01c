"""Device time of the outer exchange, per outer sync (ms).

The ops under the program's ``pier.outer.exchange`` scope (each Δθ leaf's
reduction across groups, ``sync/strategies.py``, and its staging), inside
runs of the eager outer step ``jit_outer_fn`` (``bench/progtrace.py``).
"""

import progtrace

MODULE = "jit_outer_fn"


def read(ctx):
    win = progtrace.window(ctx)
    ms = win.per_run_ms(
        MODULE, lambda scope, _backward: scope == "pier.outer.exchange")
    ctx["log"](f"outer_exchange_device_ms: {ms} ms a {MODULE} run, "
               f"{win.module_runs(MODULE):.0f} runs")
    return ms
