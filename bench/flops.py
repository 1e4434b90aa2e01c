"""Operations and bytes the benchmark's work needs, counted from shapes.

Nothing here reads the program: every count follows from a configuration
file (``bench/configs/<name>.json``) and the cell's shapes, so each PR
computes a roofline share or a utilization the same way.

``peak(device_kind)`` reads ``bench/peaks.json``; a chip that is not in the
table is an error, never a default.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind`` (bench/peaks.json)."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["num_heads"]


def matmul_params(cfg: dict) -> int:
    """Parameters that enter a matrix multiplication once per token.

    Attention projections and the MLP of every layer, and the output
    projection (tied to the token embedding or not). Position embeddings,
    norms and the embedding gather do not count.
    """
    d, hd = cfg["d_model"], head_dim(cfg)
    attn = d * hd * (2 * cfg["num_heads"] + 2 * cfg["num_kv_heads"])
    mlp = (3 if cfg["activation"] == "swiglu" else 2) * d * cfg["d_ff"]
    return cfg["num_layers"] * (attn + mlp) + cfg["vocab_size"] * d


def param_count(cfg: dict) -> int:
    """Every parameter the model holds (the outer step reads each once)."""
    d = cfg["d_model"]
    norm = 2 * d if cfg["norm"] == "layernorm" else d
    n = cfg["vocab_size"] * d + norm  # token embedding, final norm
    if cfg["positional"] == "learned":
        n += cfg["max_position_embeddings"] * d
    if not cfg["tie_embeddings"]:
        n += cfg["vocab_size"] * d
    d_attn = d * head_dim(cfg) * (2 * cfg["num_heads"]
                                  + 2 * cfg["num_kv_heads"])
    mlp = (3 if cfg["activation"] == "swiglu" else 2) * d * cfg["d_ff"]
    return n + cfg["num_layers"] * (d_attn + mlp + 2 * norm)


def model_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Training FLOPs per token: forward and backward, nothing recomputed.

    6·N for the matrix multiplications (N = :func:`matmul_params`) plus
    6·L·s·(H·hd) for causal attention: QKᵀ and PV cost 2·s·(H·hd) each per
    token over a full context, causality halves that, and the backward
    pass doubles the forward.
    """
    attn_width = cfg["num_heads"] * head_dim(cfg)
    return (6.0 * matmul_params(cfg)
            + 6.0 * cfg["num_layers"] * seq_len * attn_width)


def flash_attention_fwd(batch: int, seq: int, heads: int, hd: int,
                        kv_heads: int | None = None,
                        itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one causal attention forward call.

    Query i attends to keys 0..i: s(s+1)/2 pairs per head, each costing
    2·hd for QKᵀ and 2·hd for PV. Bytes: q, k and v read once and the
    output written once, in the compute dtype.
    """
    kv_heads = kv_heads or heads
    pairs = seq * (seq + 1) / 2
    flops = 4.0 * batch * heads * hd * pairs
    nbytes = itemsize * batch * seq * hd * (2 * heads + 2 * kv_heads)
    return flops, float(nbytes)


def outer_step_bytes(n_params: int) -> float:
    """HBM bytes the outer step needs over ``n_params`` fp32 parameters.

    It reads each parameter, its anchor and its momentum (Δθ is their
    difference) and writes the new parameter, momentum and anchor: 3 reads
    and 3 writes of 4 bytes, 24 B per parameter.
    """
    return 24.0 * n_params


def roofline_time(flops: float, nbytes: float, pk: dict) -> tuple[float, str]:
    """Least time on one chip and which bound sets it ("compute"/"memory")."""
    t_c = flops / pk["bf16_flop_per_s"]
    t_m = nbytes / pk["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
