"""Fused Pier outer-update Pallas kernel (Alg. 2 lines 20-21).

The unfused update reads θ_anchor, M, Δθ and writes θ', M', anchor' as six
separate HBM-bound elementwise ops (XLA usually fuses some but keeps fp32
temporaries). This kernel streams one (block,) panel of each operand through
VMEM and emits both outputs in a single pass — the op is purely
memory-bandwidth-bound, so one fused pass is its roofline.

μ and lr arrive as (1, 1) SMEM scalars so one compiled kernel serves every
step of the μ-decay / outer-LR schedules (no recompilation when they change).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import mosaic_call, out_struct, resolve_kernel
from repro.kernels.ref import pier_update_ref

_BLOCK = 4096  # lanes*32 panels: multiple of the (8,128) fp32 VMEM tile


def _update_kernel(mu_ref, lr_ref, a_ref, m_ref, d_ref, p_out, m_out, *,
                   formulation: str):
    mu = mu_ref[0, 0]
    lr = lr_ref[0, 0]
    a = a_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)
    m_new = mu * m + d
    if formulation == "nesterov_torch":
        step = mu * m_new + d
    elif formulation == "nesterov_classic":
        step = mu * m + d
    else:  # sgd
        step = m_new
    p_out[...] = (a + lr * step).astype(p_out.dtype)
    m_out[...] = m_new.astype(m_out.dtype)


def pier_update(
    anchor: jax.Array,  # flattened (N,) — any dtype
    momentum: jax.Array,  # (N,)
    delta: jax.Array,  # (N,)
    mu: jax.Array,  # scalar
    lr: jax.Array,  # scalar
    *,
    formulation: str = "nesterov_torch",
    block: int = _BLOCK,
    interpret: Optional[bool] = None,
):
    """Returns (new_params_f32, new_momentum) for one flat leaf.

    ``interpret=None`` dispatches through the KernelBackend registry:
    compiled Mosaic on tpu-mosaic, the interpreter off-accelerator, and
    the jnp oracle on gpu-triton (SMEM scalars don't lower to Triton) and
    jnp-ref. An explicit bool forces the Pallas body (legacy override).
    """
    impl, interpret = resolve_kernel("pier_update", interpret)
    if impl == "jnp":
        return _pier_update_jnp(anchor, momentum, delta, mu, lr,
                                formulation=formulation)
    return mosaic_call(functools.partial(
        _pier_update_pallas, formulation=formulation, block=block,
        interpret=interpret), anchor, momentum, delta, mu, lr,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("formulation",))
def _pier_update_jnp(anchor, momentum, delta, mu, lr, *, formulation):
    p, m = pier_update_ref(anchor, momentum, delta, mu=mu, lr=lr,
                           formulation=formulation)
    # match the kernel's output dtypes: p fp32, m in the momentum dtype
    return p, m.astype(momentum.dtype)


@functools.partial(
    jax.jit, static_argnames=("formulation", "block", "interpret"))
def _pier_update_pallas(anchor, momentum, delta, mu, lr, *,
                        formulation, block, interpret):
    (n,) = anchor.shape
    np_ = ((n + block - 1) // block) * block
    if np_ != n:
        anchor, momentum, delta = (
            jnp.pad(t, (0, np_ - n)) for t in (anchor, momentum, delta))
    grid = (np_ // block,)
    mu2 = jnp.asarray(mu, jnp.float32).reshape(1, 1)
    lr2 = jnp.asarray(lr, jnp.float32).reshape(1, 1)

    p_new, m_new = pl.pallas_call(
        functools.partial(_update_kernel, formulation=formulation),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_shape=[
            out_struct((np_,), jnp.float32, anchor, momentum, delta),
            out_struct((np_,), momentum.dtype, anchor, momentum, delta),
        ],
        interpret=interpret,
        name="pier_update",
    )(mu2, lr2, anchor, momentum, delta)
    return p_new[:n], m_new[:n]
