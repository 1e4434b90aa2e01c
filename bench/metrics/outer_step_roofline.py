"""Share of the outer step's memory roofline, per sync (%).

The eager outer step (``parallel/steps.py``, XLA module ``jit_outer_fn``)
must read each parameter, its anchor and its momentum and write the new
parameter, momentum and anchor: 24 bytes per fp32 parameter
(``bench/flops.py``). Its least time is those bytes over the HBM
bandwidth; the share is that over the module's device time. The fused
``pier_update`` kernel does its arithmetic inside this step; its own
events cannot carry a roofline, since XLA stages its operands outside the
kernel's time.
"""

import flops

MODULE = "jit_outer_fn"


def read(ctx):
    secs, syncs = ctx["trace"].modules(MODULE)
    if not syncs or secs <= 0:
        return None
    pk = flops.peak(ctx["device_kind"])
    nbytes = flops.outer_step_bytes(flops.param_count(ctx["cell"].config))
    return 100.0 * syncs * nbytes / pk["hbm_bytes_per_s"] / secs
