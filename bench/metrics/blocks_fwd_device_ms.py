"""Device time of the model blocks' forward pass, per inner step (ms).

The ops that the program's ``pier.blocks`` scope covers (the embedding,
every decoder layer and the final norm, ``models/transformer.py``) and
that are not under a ``transpose(...)`` of the backward pass, inside runs
of the inner-step module ``jit_stepfn``; read from each op's name-stack
path in the trace (``bench/progtrace.py``).
"""

import progtrace

MODULE = "jit_stepfn"


def read(ctx):
    ms = progtrace.window(ctx).per_run_ms(
        MODULE, lambda scope, backward: scope == "pier.blocks"
        and not backward)
    ctx["log"](f"blocks_fwd_device_ms: {ms} ms a {MODULE} run")
    return ms
