"""Device time of the model blocks' backward pass, per inner step (ms).

The ops under ``transpose(jvp(pier.blocks))``: the gradient of the
embedding, every decoder layer and the final norm
(``models/transformer.py``), inside runs of ``jit_stepfn``; read from each
op's name-stack path in the trace (``bench/progtrace.py``).
"""

import progtrace

MODULE = "jit_stepfn"


def read(ctx):
    ms = progtrace.window(ctx).per_run_ms(
        MODULE, lambda scope, backward: scope == "pier.blocks" and backward)
    ctx["log"](f"blocks_bwd_device_ms: {ms} ms a {MODULE} run")
    return ms
