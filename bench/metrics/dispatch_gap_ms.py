"""Device idle time while the host dispatches a step, per step (ms).

The first device's idle time inside the trainer's ``trainer.dispatch``
spans (``launch/train.py``: from the start of ``Trainer.train_step`` until
the inner step is enqueued), over the number of those spans
(``bench/progtrace.py``).
"""

import progtrace

SPAN = "trainer.dispatch"


def read(ctx):
    secs, steps = progtrace.window(ctx).idle_in(SPAN)
    ctx["log"](f"dispatch_gap_ms: {secs:.6f} s idle in {steps} {SPAN} spans")
    return 1e3 * secs / steps if steps else None
