"""Jit'd wrappers: the integration surface between kernels and the system.

Every wrapper dispatches through the KernelBackend registry
(kernels/backend.py): each kernel entry point resolves its lane (compiled
Pallas, interpreted Pallas, or the jnp oracle) from the process-wide
backend and its per-kernel capability table — there is no ``interpret``
threading here anymore.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import paged_decode_attention as _paged_decode
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.pier_update import pier_update as _pier_update
from repro.kernels.quantize import (dequantize_blockwise as _dequantize,
                                    quantize_blockwise as _quantize)
from repro.kernels.ref import flash_attention_ref
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


def flash_attention_supported(q, k, v, *, window: int = 0,
                              softcap: float = 0.0) -> bool:
    B, S, H, hd = q.shape
    if hd % 8 != 0 or hd > 256:
        return False
    if k.shape[2] and q.shape[2] % k.shape[2] != 0:
        return False
    return S >= 16


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0):
    """Flash attention forward; differentiable for the training step.

    The kernel has no backward of its own: the gradient is the VJP of the
    ``kernels/ref.py`` oracle, recomputed from q, k and v.
    """
    return _trainable_flash(q, k, v, causal, window, softcap)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _trainable_flash(q, k, v, causal, window, softcap):
    return _flash(
        q, k, v, causal=causal, window=window, softcap=softcap,
        block_q=128, block_kv=128)


def _trainable_flash_fwd(q, k, v, causal, window, softcap):
    return _trainable_flash(q, k, v, causal, window, softcap), (q, k, v)


def _trainable_flash_bwd(causal, window, softcap, res, g):
    _, vjp = jax.vjp(functools.partial(
        flash_attention_ref, causal=causal, window=window, softcap=softcap),
        *res)
    return vjp(g)


_trainable_flash.defvjp(_trainable_flash_fwd, _trainable_flash_bwd)


# ---------------------------------------------------------------------------
# paged decode attention (serving, DESIGN.md §12)
# ---------------------------------------------------------------------------


def paged_decode_attention(q, k_pool, v_pool, block_tables, context_lens,
                           k_scales=None, v_scales=None, *,
                           window: int = 0, softcap: float = 0.0):
    """Single-query attention through a block table (kernels/decode_attention).

    q (B, H, hd); pools (N, bs, Hkv, hd) [+ (N, bs, Hkv) fp32 scales when
    int8-quantized]; block_tables (B, T) int32; context_lens (B,) int32.
    """
    return _paged_decode(
        q, k_pool, v_pool, block_tables, context_lens, k_scales, v_scales,
        window=window, softcap=softcap)


# ---------------------------------------------------------------------------
# fused Pier outer update (over whole pytrees)
# ---------------------------------------------------------------------------


def pier_update_leaf(a, m, d, tc, *, mu, lr):
    """Fused Pier outer update on one leaf (any shape) -> (p_f32, m_new).

    The single-leaf building block of ``core.outer.outer_reduce_leaves``
    (the use_pallas path of both the fused and the chunked span-wise
    outer reduce).
    """
    shape = m.shape
    p1, m1 = _pier_update(
        a.reshape(-1), m.reshape(-1), d.reshape(-1),
        jnp.asarray(mu, jnp.float32), jnp.asarray(lr, jnp.float32),
        formulation=tc.outer_optimizer)
    return p1.reshape(shape), m1.reshape(shape).astype(m.dtype)


# ---------------------------------------------------------------------------
# blockwise Δθ quantize / dequantize (compressed outer collective)
# ---------------------------------------------------------------------------


def quantize_blockwise(x, *, bits: int = 8, block: int = 256):
    """Flat (N,) -> (q int8 (nblocks*block,), scales f32 (nblocks,))."""
    return _quantize(x, bits=bits, block=block)


def dequantize_blockwise(q, scales, *, block: int = 256):
    """Inverse of :func:`quantize_blockwise` (padded payload, fp32)."""
    return _dequantize(q, scales, block=block)


# NOTE: the int8-wire ring all-reduce (kernels/ring_allreduce.py) is NOT
# wrapped here: its transport resolves backend-aware from the strategy's
# ReduceCtx (use_pallas + resolve_transport), not per-call, so the
# Int8Wire strategy imports it directly.


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------


def rmsnorm(x, scale, *, eps: float = 1e-5):
    return _rmsnorm(x, scale, eps=eps)
