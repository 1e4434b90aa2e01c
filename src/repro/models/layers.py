"""Shared building blocks: norms, MLPs, embeddings, initializers.

Everything is functional: ``init_*`` builds a param dict, ``apply`` functions
take ``(params, x, cfg)``. Params are stored in ``cfg.param_dtype`` (fp32
master weights, as the paper's mixed-precision recipe prescribes) and cast to
``cfg.dtype`` (bf16) on use.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.parallel.axes import logical_constraint


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, shape, scale: Optional[float] = None, dtype=jnp.float32):
    """Truncated-normal init; default std 0.02 (GPT-2 / Megatron convention)."""
    std = 0.02 if scale is None else scale
    return std * jax.random.truncated_normal(key, -3.0, 3.0, shape, dtype)


def out_proj_init(key, shape, num_layers: int, dtype=jnp.float32):
    """Residual-branch output proj init, scaled by 1/sqrt(2L) (GPT-2)."""
    std = 0.02 / math.sqrt(2 * max(num_layers, 1))
    return std * jax.random.truncated_normal(key, -3.0, 3.0, shape, dtype)


def compute_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def vary_like(tree, ref):
    """Give constant-initialized scan carries the same varying-manual-axes
    (VMA) annotation as ``ref``. Under partial-manual shard_map a
    ``lax.scan`` carry must match its body output's varying axes; adding a
    zero derived from ``ref`` transfers the annotation at zero cost (XLA
    folds the empty-slice sum away)."""
    zero = jnp.sum(ref[:0].astype(jnp.float32))
    return jax.tree.map(lambda t: t + zero.astype(t.dtype), tree)


def cast(params_leaf, cfg: ModelConfig):
    return params_leaf.astype(compute_dtype(cfg))


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def init_norm(key, cfg: ModelConfig, dim: Optional[int] = None):
    dim = dim or cfg.d_model
    p = {"scale": jnp.ones((dim,), jnp.dtype(cfg.param_dtype))}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((dim,), jnp.dtype(cfg.param_dtype))
    return p


def apply_norm(p, x, cfg: ModelConfig):
    """RMSNorm or LayerNorm computed in fp32, cast back to compute dtype."""
    orig_dtype = x.dtype
    x = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        y = x * jax.lax.rsqrt(ms + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32)
    return y.astype(orig_dtype)


def rms_norm_headwise(x, scale, eps: float = 1e-6):
    """Per-head qk-norm (Qwen3/Chameleon): normalize over head_dim."""
    orig = x.dtype
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(orig)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    pd = jnp.dtype(cfg.param_dtype)
    p = {}
    if cfg.activation == "swiglu":
        p["w_gate"] = dense_init(ks[0], (cfg.d_model, d_ff), dtype=pd)
        p["w_up"] = dense_init(ks[1], (cfg.d_model, d_ff), dtype=pd)
    else:  # gelu
        p["w_up"] = dense_init(ks[1], (cfg.d_model, d_ff), dtype=pd)
    p["w_down"] = out_proj_init(ks[2], (d_ff, cfg.d_model), cfg.num_layers, dtype=pd)
    return p


def apply_mlp(p, x, cfg: ModelConfig):
    """Position-wise MLP. x: (..., d_model)."""
    up = jnp.einsum("...d,df->...f", x, cast(p["w_up"], cfg))
    if cfg.activation == "swiglu":
        gate = jnp.einsum("...d,df->...f", x, cast(p["w_gate"], cfg))
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(up)
    if h.ndim == 3:
        h = logical_constraint(h, "batch", None, "tp")
    out = jnp.einsum("...f,fd->...d", h, cast(p["w_down"], cfg))
    return out


# ---------------------------------------------------------------------------
# embeddings / LM head
# ---------------------------------------------------------------------------


def init_embeddings(key, cfg: ModelConfig):
    ks = jax.random.split(key, 3)
    pd = jnp.dtype(cfg.param_dtype)
    p = {"tokens": dense_init(ks[0], (cfg.vocab_size, cfg.d_model), dtype=pd)}
    if cfg.positional == "learned":
        p["positions"] = dense_init(
            ks[1], (cfg.max_position_embeddings, cfg.d_model), dtype=pd
        )
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[2], (cfg.d_model, cfg.vocab_size), dtype=pd)
    return p


def embed_tokens(p, tokens, cfg: ModelConfig, *, position_offset=0):
    """tokens: (B, S) int32 -> (B, S, D)."""
    x = jnp.take(cast(p["tokens"], cfg), tokens, axis=0)
    if cfg.positional == "learned":
        positions = position_offset + jnp.arange(tokens.shape[-1])
        x = x + jnp.take(cast(p["positions"], cfg), positions, axis=0)[None]
    x = logical_constraint(x, "batch", None, None)
    return x


@jax.named_scope("pier.head")
def lm_logits(p, x, cfg: ModelConfig):
    """x: (B, S, D) -> (B, S, V); fp32 logits for a stable loss."""
    table = p["tokens"].T if cfg.tie_embeddings else p["lm_head"]
    logits = jnp.einsum(
        "bsd,dv->bsv", x.astype(jnp.float32), table.astype(jnp.float32)
    )
    if cfg.logit_softcap > 0:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    logits = logical_constraint(logits, "batch", None, "tp")
    return logits


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x, positions, theta: float):
    """x: (B, S, H, hd); positions: (S,) or (B, S)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)  # (hd/2,)
    if positions.ndim == 1:
        angles = positions[:, None].astype(jnp.float32) * freqs[None, :]  # (S, hd/2)
        angles = angles[None, :, None, :]  # (1, S, 1, hd/2)
    else:
        angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
        angles = angles[:, :, None, :]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return rotated.astype(x.dtype)
