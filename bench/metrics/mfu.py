"""Model FLOP utilization of the whole training step (%).

Model FLOPs per token (``bench/flops.py``: 6·N plus causal attention, no
recomputation counted) times the traced run's tokens per second, over the
chips' bf16 peak for their ``device_kind``.
"""

import flops


def read(ctx):
    cfg, tr = ctx["cell"].config, ctx["cell"].traffic
    per_token = flops.model_flops_per_token(cfg, tr["seq_len"])
    peak = flops.peak(ctx["device_kind"])["bf16_flop_per_s"]
    return 100.0 * per_token * ctx["tokens_per_s"] / (ctx["chips"] * peak)
