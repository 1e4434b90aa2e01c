"""Device time of the logits and the loss, forward and backward, per inner
step (ms).

The ops under the program's ``pier.head`` scope (``models/layers.py``
``lm_logits``: the logits and their softcap; ``models/registry.py``: the
log-sum-exp, the gold logit and the mean negative log-likelihood), in both
passes, inside runs of ``jit_stepfn`` (``bench/progtrace.py``).
"""

import progtrace

MODULE = "jit_stepfn"


def read(ctx):
    win = progtrace.window(ctx)
    fwd = win.per_run_ms(MODULE, lambda s, b: s == "pier.head" and not b)
    ms = win.per_run_ms(MODULE, lambda s, _b: s == "pier.head")
    ctx["log"](f"head_device_ms: {ms} ms a {MODULE} run, "
               f"{fwd} ms of it forward")
    return ms
