"""Share of the causal attention forward kernel's roofline (%).

The least time the chip needs for the calls' required work (the larger of
FLOPs over the bf16 peak and bytes over the HBM bandwidth, counted from
the shapes by ``bench/flops.py``) over the kernel's device time in the
trace (``kernels/flash_attention.py``). Each device runs one call per layer
and step over its own rows. Where the kernel is not in the compiled step,
nothing is read.
"""

import flops

KERNEL = "_flash_attention_pallas"


def match(name):
    return name.startswith(KERNEL)


def read(ctx):
    secs, calls = ctx["trace"].ops(match)
    if not calls or secs <= 0:
        return None
    cfg, tr = ctx["cell"].config, ctx["cell"].traffic
    rows = tr["global_batch"] // (tr["mesh"][0] * tr["mesh"][1])
    f, b = flops.flash_attention_fwd(
        rows, tr["seq_len"], cfg["num_heads"], flops.head_dim(cfg),
        cfg["num_kv_heads"])
    least, bound = flops.roofline_time(f, b, flops.peak(ctx["device_kind"]))
    ctx["log"](f"flash_attention_fwd: {calls:.0f} calls, {secs:.6f} s, "
               f"bound by {bound}")
    return 100.0 * calls * least / secs
