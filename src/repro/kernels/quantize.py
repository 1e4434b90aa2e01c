"""Blockwise Δθ quantize / dequantize Pallas kernels (DESIGN.md §6).

The compressed outer collective sends the cross-pod Δθ payload as int8 (or
int4-in-int8, modeling 2x packing) with one fp32 absmax scale per
``block`` contiguous elements:

    scale_b = max|x_b| / qmax          qmax = 2^(bits-1) - 1
    q_b     = clip(round(x_b / scale_b), -qmax, qmax)

Symmetric, zero-point-free: a zero block quantizes to zeros exactly (the
scale is 0 and the inverse is masked), so momentum-free leaves cost nothing
in error. Both kernels stream (rows, block) panels through VMEM — the op is
purely memory-bound, one pass is its roofline. ``block`` should be a
multiple of 128 (lane width) on a real TPU; the interpreter accepts any.
Scales travel as an ``(nblocks, 1)`` column inside the kernels: Mosaic
refuses a 1-D block of ``_ROWS`` (not a multiple of 128), while an
``(_ROWS, 1)`` block spans the whole minor dim.

The pure-jnp oracles live in kernels/ref.py; the kernels execute the same
ops elementwise so interpret-mode output matches the oracle bit for bit.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import mosaic_call, out_struct, resolve_kernel
from repro.kernels.ref import dequantize_blockwise_ref, quantize_blockwise_ref

_ROWS = 8  # quant blocks (= scale rows) per grid step: fp32 sublane tile


def _quant_kernel(x_ref, q_ref, s_ref, *, qmax: float):
    x = x_ref[...].astype(jnp.float32)  # (R, B)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)  # (R, 1)
    # reciprocal-multiply, NOT division: XLA strength-reduces constant
    # divisions under jit but not eagerly, and the oracle must match bitwise
    scale = absmax * (1.0 / qmax)
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    q = jnp.clip(jnp.round(x * inv), -qmax, qmax)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)  # (R, B)
    o_ref[...] = q * s_ref[...]  # (R, B) * (R, 1)


def _pad_rows(nb: int) -> int:
    return ((nb + _ROWS - 1) // _ROWS) * _ROWS


def quantize_blockwise(
    x: jax.Array,  # flattened (N,) — any float dtype
    *,
    bits: int = 8,
    block: int = 256,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (q int8 (nblocks*block,), scales f32 (nblocks,)).

    The payload is padded to whole blocks; callers slice the dequantized
    result back to N. ``interpret=None`` dispatches through the
    KernelBackend registry (compiled/interpreted Pallas or the jnp
    oracle); an explicit bool forces the Pallas body (legacy override).
    """
    impl, interpret = resolve_kernel("quantize", interpret)
    if impl == "jnp":
        return _quantize_jnp(x, bits=bits, block=block)
    return mosaic_call(functools.partial(
        _quantize_pallas, bits=bits, block=block, interpret=interpret),
        x, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bits", "block"))
def _quantize_jnp(x, *, bits, block):
    return quantize_blockwise_ref(x, bits=bits, block=block)


@functools.partial(
    jax.jit, static_argnames=("bits", "block", "interpret"))
def _quantize_pallas(x, *, bits, block, interpret):
    qmax = float(2 ** (bits - 1) - 1)
    (n,) = x.shape
    nb = (n + block - 1) // block
    if nb * block != n:
        x = jnp.pad(x, (0, nb * block - n))
    nbp = _pad_rows(nb)
    x2 = x.reshape(nb, block)
    if nbp != nb:
        x2 = jnp.pad(x2, ((0, nbp - nb), (0, 0)))
    q, s = pl.pallas_call(
        functools.partial(_quant_kernel, qmax=qmax),
        grid=(nbp // _ROWS,),
        in_specs=[pl.BlockSpec((_ROWS, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((_ROWS, block), lambda i: (i, 0)),
            pl.BlockSpec((_ROWS, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            out_struct((nbp, block), jnp.int8, x2),
            out_struct((nbp, 1), jnp.float32, x2),
        ],
        interpret=interpret,
    )(x2)
    return q[:nb].reshape(nb * block), s[:nb, 0]


def dequantize_blockwise(
    q: jax.Array,  # (nblocks*block,) int8
    scales: jax.Array,  # (nblocks,) f32
    *,
    block: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Inverse of :func:`quantize_blockwise`; returns fp32 (nblocks*block,)."""
    impl, interpret = resolve_kernel("dequantize", interpret)
    if impl == "jnp":
        return _dequantize_jnp(q, scales, block=block)
    return mosaic_call(functools.partial(
        _dequantize_pallas, block=block, interpret=interpret),
        q, scales, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block",))
def _dequantize_jnp(q, scales, *, block):
    return dequantize_blockwise_ref(q, scales, block=block)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _dequantize_pallas(q, scales, *, block, interpret):
    (nq,) = q.shape
    nb = nq // block
    if nb * block != nq:
        raise ValueError(
            f"ragged quantized payload: {nq} values do not fill whole "
            f"blocks of {block} (quantize_blockwise pads to whole blocks; "
            f"pass its output unsliced)")
    nbp = _pad_rows(nb)
    q2 = q.reshape(nb, block)
    s = scales
    if nbp != nb:
        q2 = jnp.pad(q2, ((0, nbp - nb), (0, 0)))
        s = jnp.pad(s, (0, nbp - nb))
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(nbp // _ROWS,),
        in_specs=[
            pl.BlockSpec((_ROWS, block), lambda i: (i, 0)),
            pl.BlockSpec((_ROWS, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_ROWS, block), lambda i: (i, 0)),
        out_shape=out_struct((nbp, block), jnp.float32, q2, s),
        interpret=interpret,
    )(q2, s[:, None])
    return out[:nb].reshape(nb * block)
