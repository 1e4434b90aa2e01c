"""RMSNorm Pallas TPU kernel: fused mean-of-squares + scale in one VMEM pass.

Row-blocked: each grid step normalizes a (block_rows, D) panel. The reduction
runs in fp32 VREGs; the output is cast back to the input dtype. Replaces the
three-op XLA pattern (square-reduce / rsqrt-broadcast / multiply) that makes
two HBM round trips over the activation.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import mosaic_call, out_struct, resolve_kernel
from repro.kernels.ref import rmsnorm_ref


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (
        x * jax.lax.rsqrt(ms + eps) * s_ref[...].astype(jnp.float32)
    ).astype(o_ref.dtype)


def rmsnorm(
    x: jax.Array,  # (..., D)
    scale: jax.Array,  # (D,)
    *,
    eps: float = 1e-5,
    block_rows: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused RMSNorm. ``interpret=None`` dispatches through the
    KernelBackend registry (the body is plain-BlockSpec, so it compiles on
    both tpu-mosaic and gpu-triton); an explicit bool forces the Pallas
    body (legacy override)."""
    impl, interpret = resolve_kernel("rmsnorm", interpret)
    if impl == "jnp":
        return _rmsnorm_jnp(x, scale, eps=eps)
    return mosaic_call(functools.partial(
        _rmsnorm_pallas, eps=eps, block_rows=block_rows,
        interpret=interpret), x, scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("eps",))
def _rmsnorm_jnp(x, scale, *, eps):
    return rmsnorm_ref(x, scale, eps=eps)


@functools.partial(
    jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def _rmsnorm_pallas(x, scale, *, eps, block_rows, interpret):
    orig_shape = x.shape
    D = x.shape[-1]
    rows = 1
    for d in x.shape[:-1]:
        rows *= d
    x2 = x.reshape(rows, D)
    block_rows = max(min(block_rows, rows), 1)
    rp = ((rows + block_rows - 1) // block_rows) * block_rows
    if rp != rows:
        x2 = jnp.pad(x2, ((0, rp - rows), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(rp // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        out_shape=out_struct((rp, D), x.dtype, x2, scale),
        interpret=interpret,
        name="rmsnorm",
    )(x2, scale)
    return out[:rows].reshape(orig_shape)
