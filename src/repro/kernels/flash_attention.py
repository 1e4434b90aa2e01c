"""Flash-attention Pallas TPU kernel (FlashAttention-2 analogue, paper §V).

TPU adaptation of the CUDA algorithm: instead of warps/shared-memory tiles,
the kernel tiles (block_q × head_dim) query panels and (block_kv × head_dim)
KV panels into VMEM with an online-softmax accumulator in VMEM scratch, and
drives the MXU with 128-aligned matmul panels. The KV axis is the innermost
*sequential* grid dimension, so the running (m, l, acc) state lives in VMEM
scratch across grid steps — the TPU-idiomatic replacement for the CUDA inner
loop (there is no warp-shuffle analogue; the online-softmax reduction is a
VREG reduction instead).

GQA is handled in the BlockSpec index maps (kv block index = h // group), so
KV panels are never replicated to the full head count in HBM.

Supports causal masking, sliding windows, and logit soft-capping. Causal
panels strictly above the diagonal are skipped with ``pl.when`` (no MXU work
issued), which on TPU halves the effective FLOPs exactly as FA-2's block
skipping does on SMs.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import mosaic_call, out_struct, resolve_kernel
from repro.kernels.ref import flash_attention_ref

# jax < 0.5 names this TPUCompilerParams; it was renamed to CompilerParams.
_CompilerParams = getattr(pltpu, "CompilerParams", None) or getattr(
    pltpu, "TPUCompilerParams")

NEG_INF = -1e30


def _attn_kernel(
    q_ref, k_ref, v_ref,  # VMEM tiles
    o_ref,  # output tile
    m_scr, l_scr, acc_scr,  # VMEM scratch: (bq,1), (bq,1), (bq, hd)
    *,
    scale: float,
    block_q: int,
    block_kv: int,
    seq_len: int,
    causal: bool,
    window: int,
    softcap: float,
    num_kv_blocks: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute():
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0)
        k_pos = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        mask = k_pos < seq_len  # exclude padded kv positions
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= (q_pos - k_pos) < window

        q = q_ref[0, 0].astype(jnp.float32)  # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (bkv, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bkv)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]  # (bq, 1)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (bq, bkv)
        alpha = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc

    if causal:
        # skip panels entirely above the causal diagonal
        last_q = qi * block_q + block_q - 1
        first_k = ki * block_kv

        @pl.when(last_q >= first_k)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, ...] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # (B, S, H, hd)
    k: jax.Array,  # (B, S, Hkv, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Pallas flash attention. Returns (B, S, H, hd) in q.dtype.

    ``interpret=None`` dispatches through the KernelBackend registry —
    compiled Mosaic on tpu-mosaic (the old hardcoded ``interpret=True``
    default meant direct callers never compiled on real TPUs), the
    interpreter off-accelerator, the jnp oracle on gpu-triton/jnp-ref
    (VMEM scratch + dimension_semantics don't lower to Triton). An
    explicit bool forces the Pallas body (legacy override).
    """
    impl, interpret = resolve_kernel("flash_attention", interpret)
    if impl == "jnp":
        return _flash_attention_jnp(q, k, v, causal=causal, window=window,
                                    softcap=softcap)
    return mosaic_call(functools.partial(
        _flash_attention_pallas, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_kv=block_kv,
        interpret=interpret), q, k, v, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap"))
def _flash_attention_jnp(q, k, v, *, causal, window, softcap):
    return flash_attention_ref(q, k, v, causal=causal, window=window,
                               softcap=softcap)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_kv",
                     "interpret"),
)
def _flash_attention_pallas(q, k, v, *, causal, window, softcap,
                            block_q, block_kv, interpret):
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    assert H % Hkv == 0, (H, Hkv)
    G = H // Hkv
    scale = 1.0 / math.sqrt(hd)

    block_q = max(min(block_q, S), 8)
    block_kv = max(min(block_kv, S), 8)
    Sp = ((S + block_q - 1) // block_q) * block_q
    Sp = ((Sp + block_kv - 1) // block_kv) * block_kv

    # (B, H, S, hd) layout: head-major so a (block, hd) tile is contiguous
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if Sp != S:
        pad = ((0, 0), (0, 0), (0, Sp - S), (0, 0))
        qt, kt, vt = (jnp.pad(t, pad) for t in (qt, kt, vt))

    nq = Sp // block_q
    nk = Sp // block_kv
    grid = (B, H, nq, nk)

    kernel = functools.partial(
        _attn_kernel, scale=scale, block_q=block_q, block_kv=block_kv,
        seq_len=S, causal=causal, window=window, softcap=softcap,
        num_kv_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=out_struct((B, H, Sp, hd), q.dtype, qt, kt, vt),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        # the trace's name for the kernel, which the benchmark's roofline
        # reader of the attention forward matches
        name="_flash_attention_pallas",
    )(qt, kt, vt)
    return jnp.moveaxis(out[:, :, :S], 1, 2)
