"""Device idle time while the host handles an outer sync, per sync (ms).

The first device's idle time inside the trainer's ``trainer.outer`` spans
(``launch/train.py``: a step's outer events, the eager outer step or its
dispatch and apply, the offload moves and the controller round), over the
number of those spans: one per sync at ``sync_delay`` 0
(``bench/progtrace.py``).
"""

import progtrace

SPAN = "trainer.outer"


def read(ctx):
    secs, syncs = progtrace.window(ctx).idle_in(SPAN)
    ctx["log"](f"sync_gap_ms: {secs:.6f} s idle in {syncs} {SPAN} spans")
    return 1e3 * secs / syncs if syncs else None
