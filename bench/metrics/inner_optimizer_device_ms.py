"""Device time of the inner optimizer, per inner step (ms).

The ops under the program's ``pier.optimizer`` scope (``parallel/steps.py``:
the gradient clip, the AdamW update of ``optim/`` and the metrics'
means), inside runs of ``jit_stepfn`` (``bench/progtrace.py``).
"""

import progtrace

MODULE = "jit_stepfn"


def read(ctx):
    ms = progtrace.window(ctx).per_run_ms(
        MODULE, lambda scope, _backward: scope == "pier.optimizer")
    ctx["log"](f"inner_optimizer_device_ms: {ms} ms a {MODULE} run")
    return ms
