"""Paged decode-attention Pallas TPU kernel (serving, DESIGN.md §12).

Single-query attention for continuous-batching decode: each sequence's K/V
lives in fixed-size *blocks* scattered through a shared pool, addressed by a
per-sequence block table. The prefill-shaped ``flash_attention`` kernel
cannot serve this access pattern — its KV BlockSpecs assume one contiguous
(B, S, Hkv, hd) buffer per sequence — so decode gets its own kernel whose
KV index map *is* the block-table gather.

Layout (one attention layer):

    q             (B, H, hd)          one new query token per sequence
    k_pool/v_pool (N, bs, Hkv, hd)    the shared block pool
    block_tables  (B, T) int32        logical block j of sequence b lives in
                                      physical block ``block_tables[b, j]``
                                      (< 0 = unallocated — never touched)
    context_lens  (B,) int32          tokens written for sequence b,
                                      *including* the query's own K/V slot

The grid is (B, Hkv, T) with the block axis innermost-sequential; the
block-table gather happens in the KV BlockSpec index maps via scalar
prefetch (``PrefetchScalarGridSpec``), so each (bs, hd) KV panel is DMA'd
straight from its pool block — the PagedAttention access pattern expressed
the TPU way. An online-softmax accumulator (m, l, acc) lives in VMEM
scratch across the sequential block steps, exactly like the prefill
kernel's inner loop; blocks at or beyond ``context_lens[b]`` are skipped
with ``pl.when`` (no MXU work), and partially-filled tail blocks are
masked by position.

int8 KV (DESIGN.md §12): pools may be stored blockwise-quantized in the
``kernels/quantize.py`` wire format — int8 values plus one fp32 absmax
scale per (block-slot, kv-head) row of ``hd`` elements. The kernel then
takes the scale panels as two extra gathered inputs and dequantizes
in-VMEM (``q.astype(f32) * scale``) — elementwise-identical to
``_dequant_kernel`` — so HBM traffic for the cache drops ~4x vs fp32.

The pure-jnp oracle ``paged_decode_attention_ref`` executes the same ops
in the same order per (b, kv-head) pair, so interpret-mode kernel output
matches it bit for bit (asserted in tests/test_serving.py). GQA/MQA share
the gather: q is reshaped (B, Hkv, G, hd) and each grid step attends one
kv head's G query heads; mha is the G == 1 case.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import mosaic_call, out_struct, resolve_kernel

# jax < 0.5 names this TPUCompilerParams; it was renamed to CompilerParams.
_CompilerParams = getattr(pltpu, "CompilerParams", None) or getattr(
    pltpu, "TPUCompilerParams")

NEG_INF = -1e30


def _decode_kernel(
    # scalar prefetch
    bt_ref,  # (B, T) int32 block tables
    cl_ref,  # (B,) int32 context lengths
    # VMEM tiles
    q_ref,  # (1, 1, G, hd)
    k_ref,  # (1, 1, bs, hd) — gathered pool block for this kv head
    v_ref,
    *rest,  # [k_scale (1, 1, bs, 1), v_scale (1, 1, bs, 1)] when quantized,
    # o_ref, m_scr (G, 1), l_scr (G, 1), acc_scr (G, hd)
    scale: float,
    block_size: int,
    window: int,
    softcap: float,
    num_blocks: int,
    quantized: bool,
):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    cl = cl_ref[b]

    @pl.when(j * block_size < cl)
    def _compute():
        G = q_ref.shape[2]
        q = q_ref[0, 0].astype(jnp.float32)  # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (bs, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        if quantized:
            # elementwise-identical to quantize._dequant_kernel
            k = k * ks_ref[0, 0]  # (bs, hd) * (bs, 1)
            v = v * vs_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (G, bs)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (G, block_size), 1)
        mask = pos < cl  # tail-block slots beyond the context
        if window > 0:
            # query position is cl - 1; same predicate as the dense path's
            # (q_pos - k_pos) < window
            mask &= pos >= cl - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]  # (G, 1)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (G, bs)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc

    @pl.when(j == num_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, ...] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,  # (B, H, hd)
    k_pool: jax.Array,  # (N, bs, Hkv, hd) — fp or int8 (with scales)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, T) int32, < 0 = unallocated
    context_lens: jax.Array,  # (B,) int32
    k_scales: Optional[jax.Array] = None,  # (N, bs, Hkv) f32 when int8
    v_scales: Optional[jax.Array] = None,
    *,
    window: int = 0,
    softcap: float = 0.0,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Paged single-query attention. Returns (B, H, hd) in q.dtype.

    Sequences with ``context_lens[b] == 0`` (empty decode slots) produce
    zeros. ``interpret=None`` dispatches through the KernelBackend
    registry: compiled Mosaic on tpu-mosaic, the interpreter
    off-accelerator, the jnp oracle on gpu-triton (scalar-prefetch grids
    don't lower to Triton) and jnp-ref. An explicit bool forces the
    Pallas body (legacy override — the bitwise tests pin
    ``interpret=True``).
    """
    impl, interpret = resolve_kernel("decode_attention", interpret)
    if impl == "jnp":
        return paged_decode_attention_ref(
            q, k_pool, v_pool, block_tables, context_lens,
            k_scales, v_scales, window=window, softcap=softcap)
    return mosaic_call(functools.partial(
        _paged_decode_pallas, window=window, softcap=softcap,
        interpret=interpret), q, k_pool, v_pool, block_tables,
        context_lens, k_scales, v_scales, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("window", "softcap", "interpret"),
)
def _paged_decode_pallas(q, k_pool, v_pool, block_tables, context_lens,
                         k_scales=None, v_scales=None, *,
                         window: int, softcap: float, interpret: bool):
    B, H, hd = q.shape
    N, bs, Hkv, _ = k_pool.shape
    assert H % Hkv == 0, (H, Hkv)
    G = H // Hkv
    T = block_tables.shape[1]
    scale = 1.0 / math.sqrt(hd)
    quantized = k_scales is not None

    q4 = q.reshape(B, Hkv, G, hd)
    block_tables = block_tables.astype(jnp.int32)
    context_lens = context_lens.astype(jnp.int32)

    def q_map(b, h, j, bt, cl):
        return (b, h, 0, 0)

    def kv_map(b, h, j, bt, cl):
        # out-of-range logical blocks clamp to physical block 0; their
        # compute is skipped (j * bs >= cl) so the gathered data is unused
        return (jnp.maximum(bt[b, j], 0), h, 0, 0)

    # Head-major views of the pools: Mosaic blocks only the two minor dims
    # whole (or in multiples of the (8, 128) tile), so one (bs, hd) tile
    # per (pool block, kv head) needs Hkv ahead of bs. The transpose is an
    # extra pass over the pool per call, the price of the cache's
    # (N, bs, Hkv, hd) layout.
    in_specs = [
        pl.BlockSpec((1, 1, G, hd), q_map),
        pl.BlockSpec((1, 1, bs, hd), kv_map),
        pl.BlockSpec((1, 1, bs, hd), kv_map),
    ]
    operands = [q4, jnp.swapaxes(k_pool, 1, 2), jnp.swapaxes(v_pool, 1, 2)]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, 1, bs, 1), kv_map),
            pl.BlockSpec((1, 1, bs, 1), kv_map),
        ]
        operands += [jnp.swapaxes(k_scales, 1, 2)[..., None],
                     jnp.swapaxes(v_scales, 1, 2)[..., None]]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, T),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, scale=scale, block_size=bs, window=window,
        softcap=softcap, num_blocks=T, quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_struct((B, Hkv, G, hd), q.dtype, *operands),
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, context_lens, *operands)
    return out.reshape(B, H, hd)


# ---------------------------------------------------------------------------
# pure-jnp oracle — same loop-body graph, per (b, kv-head) pair
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("window", "softcap"))
def _ref_pair(q, kblks, vblks, ksblks, vsblks, cl, *,
              window: int, softcap: float):
    """One (b, kv-head) pair: q (G, hd) against gathered blocks (T, bs, hd).

    Structurally mirrors the interpret-mode kernel program: an *unrolled*
    python loop over blocks (interpret mode unrolls the grid into the
    traced computation) whose per-block compute sits behind a ``lax.cond``
    on the same ``j * bs < cl`` predicate ``pl.when`` lowers to. Matching
    the program structure — not just the math — is what makes the outputs
    bitwise equal: XLA's fusion/FMA-contraction choices are
    producer-dependent (cf. the PR 4 note in kernels/ref.py), so a rolled
    scan or an eager loop drifts by ~1e-7 once the body grows a mask or a
    dequant multiply.
    """
    G, hd = q.shape
    T, bs = kblks.shape[0], kblks.shape[1]
    scale = jnp.float32(1.0 / math.sqrt(hd))
    qf = q.astype(jnp.float32)
    carry = (jnp.full((G, 1), NEG_INF, jnp.float32),
             jnp.zeros((G, 1), jnp.float32),
             jnp.zeros((G, hd), jnp.float32))
    for j in range(T):
        def compute(c, j=j):
            m, l, acc = c
            k = kblks[j].astype(jnp.float32)
            v = vblks[j].astype(jnp.float32)
            if ksblks is not None:
                # elementwise-identical to quantize._dequant_kernel
                k = k * ksblks[j][:, None]
                v = v * vsblks[j][:, None]
            s = jax.lax.dot_general(
                qf, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if softcap > 0:
                s = softcap * jnp.tanh(s / softcap)
            pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (G, bs), 1)
            mask = pos < cl
            if window > 0:
                mask &= pos >= cl - window
            s = jnp.where(mask, s, NEG_INF)
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m, m_cur)
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            acc_new = acc * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new)

        carry = jax.lax.cond(j * bs < cl, compute, lambda c: c, carry)
    m, l, acc = carry
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def paged_decode_attention_ref(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    context_lens: jax.Array,
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
    *,
    window: int = 0,
    softcap: float = 0.0,
) -> jax.Array:
    """Oracle for :func:`paged_decode_attention` (bitwise in interpret mode).

    Python loop over (b, kv head) pairs; each pair runs :func:`_ref_pair`'s
    jitted online-softmax scan over that sequence's gathered blocks.
    """
    B, H, hd = q.shape
    Hkv = k_pool.shape[2]
    G = H // Hkv
    q4 = q.reshape(B, Hkv, G, hd)
    bt = jnp.maximum(block_tables.astype(jnp.int32), 0)
    cls = context_lens.astype(jnp.int32)

    rows = []
    for b in range(B):
        kb = k_pool[bt[b]]  # (T, bs, Hkv, hd)
        vb = v_pool[bt[b]]
        ksb = k_scales[bt[b]] if k_scales is not None else None
        vsb = v_scales[bt[b]] if v_scales is not None else None
        heads = []
        for h in range(Hkv):
            heads.append(_ref_pair(
                q4[b, h], kb[:, :, h], vb[:, :, h],
                ksb[:, :, h] if ksb is not None else None,
                vsb[:, :, h] if vsb is not None else None,
                cls[b], window=window, softcap=softcap))
        rows.append(jnp.stack(heads))
    return jnp.stack(rows).reshape(B, H, hd)


def paged_decode_supported(num_heads: int, num_kv_heads: int,
                           head_dim: int) -> Tuple[bool, str]:
    """Whether the paged kernel covers this head layout (and why not)."""
    if num_kv_heads <= 0 or num_heads % num_kv_heads != 0:
        return False, f"H={num_heads} not a multiple of Hkv={num_kv_heads}"
    if head_dim > 256:
        return False, f"head_dim {head_dim} > 256"
    return True, ""
