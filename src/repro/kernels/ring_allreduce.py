"""True int8 wire format: ring exchange of quantized Δθ (DESIGN.md §8).

The compressed outer collective of §6 models int8 *numerically* but (until
PR 4) exchanged the dequantized fp32 payload — the bytes-on-wire win was
accounting, not reality. This module moves the actual ``(int8 q, fp32
scales)`` pairs across the slow exchange axes and reduces them with
**per-source-scale sum semantics**:

    Δθ_avg = (1/E) · Σ_src dequantize(q_src, s_src)        src = 0 … E−1

The sum runs in canonical source order (the linearized mesh index over the
exchange axes), so every endpoint computes bit-identical results — a hard
requirement: the reduced payload is replicated across groups (shard_map
``out_specs=P()``), and an arrival-order sum would diverge per device.

Three transports, one reduction (all reduced by the shared
:func:`repro.kernels.ref.dequant_sum_sources`, so their numerics are
identical bit for bit):

- **collective** (``"ring"``, the default off the TPU DMA lane): XLA's
  all-gather of the packed wire buffer + scales into canonical source
  slots. Runs under ``vmap(axis_name=…)`` (the single-device test
  harness) and shard_map.
- **one-hot psum**: each endpoint deposits its payload at its linearized
  slot of a zero ``(E, ·)`` buffer and psums — exact (one non-zero
  contributor per slot); a cross-check of the gather, at E× its bytes.
- **Pallas remote-DMA** (real TPU): :func:`ring_allgather_wire_tpu`
  forwards the wire buffers around the ring with
  ``pltpu.make_async_remote_copy`` (double-buffered slots, neighbor
  barrier — the guide's ring-collective pattern), then applies the same
  reduction, so the kernel only moves bytes and the numerics stay
  oracle-exact.

Wire layout: int8 values live in their int8 container; ``bits=4`` packs
two's-complement nibbles two-per-byte (:func:`pack_wire` /
:func:`unpack_wire`, exact round-trip), so the measured bytes match the
``bits/8 + 4/block`` model instead of silently shipping int8-wide int4.
:func:`measure_wire_bytes` reads the *actual* device-buffer sizes off a
real quantize+pack run — the measured (not modeled) bytes that
``benchmarks/overlap.py --json`` reports next to the analytic model.

**Reduce-scatter + all-gather wire path (DESIGN.md §14).** The all-reduce
above ships the *full* payload per device ((E−1)·P sent on the gather).
:func:`reduce_scatter_qs` / :func:`allgather_qs` split the payload into E
fixed-size per-endpoint slots (``wire_shard_blocks`` quant blocks each,
zero-padded tail, per-slot nibble packing) and move only shard-sized
buffers: endpoint e reduces slot e of all sources via the same
:func:`dequant_sum_sources` oracle, re-quantizes its reduced shard with a
second error-feedback residual, and all-gathers the (q2, s2) pair —
2·(E−1)·P/E sent per device (0.5× the all-reduce wire path at E=4).
Reconstruction is per-slot dequant + concat (:func:`dequant_concat_sources`
— no summation, bit-identical on every endpoint). The same three
transports serve both legs; the scatter leg adds
:func:`ring_scatter_wire` (stride-k ppermute, true (E−1)/E traffic),
:func:`onehot_scatter_wire` (psum correctness lane), and
:func:`shard_scatter_wire_tpu` (remote-DMA with a full entry barrier).
"""

from __future__ import annotations

import functools
import itertools
from typing import Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.kernels.backend import (COMPILED, kernel_lane, mosaic_call, on_tpu,
                                   out_struct)
from repro.kernels.ref import (dequant_concat_sources,  # noqa: F401
                               dequant_sum_sources, pack_wire,
                               shard_slot_wire, unpack_wire,
                               wire_shard_blocks)

# jax < 0.5 names this TPUCompilerParams; it was renamed to CompilerParams.
_CompilerParams = getattr(pltpu, "CompilerParams", None) or getattr(
    pltpu, "TPUCompilerParams")

# Per-pallas_call wire slice on the TPU path. All refs live in VMEM
# (Mosaic cannot index ANY-space refs directly), so one call holds
# (E + 3) × chunk bytes there: E canonical output slots + the input +
# two comm slots. 512 KiB keeps that under ~10 MiB up to E = 16.
_WIRE_CHUNK_BYTES = 1 << 19


# The wire packing (pack_wire/unpack_wire) and THE reduction
# (dequant_sum_sources — canonical-order per-source-scale sum) live in
# kernels/ref.py so the oracle, the simulator, and this transport all run
# the *identical* subgraph; this module re-exports them and owns only the
# transports (how the stacked sources are produced).

# ---------------------------------------------------------------------------
# reference transports (CPU / tier-1 / non-TPU)
# ---------------------------------------------------------------------------


def _check_axis_sizes(names, axis_sizes):
    for ax in names:
        if ax not in (axis_sizes or {}):
            raise ValueError(
                f"exchange axis {ax!r} missing from ReduceCtx.axis_sizes "
                f"(have {sorted(axis_sizes or {})}); the wire exchange "
                f"needs static ring sizes")


def _axis_idx(axis_name: str, axis_coords) -> jax.Array:
    """The caller's coordinate along one exchange axis.

    Prefer data-threaded coordinates (``ReduceCtx.axis_coords`` — an
    ``arange`` sharded over the axis, sliced per shard): jax 0.4.x lowers
    ``lax.axis_index`` inside partial-manual shard_map to a PartitionId
    instruction its SPMD partitioner rejects. Fall back to
    ``lax.axis_index`` (vmap harnesses, modern jax) when no coordinate
    was threaded.
    """
    if axis_coords and axis_name in axis_coords:
        return jnp.asarray(axis_coords[axis_name], jnp.int32)
    return jax.lax.axis_index(axis_name)


def ring_gather_wire(w: jax.Array, s: jax.Array,
                     axis_names: Sequence[str],
                     axis_sizes: Mapping[str, int]
                     ) -> Tuple[jax.Array, jax.Array]:
    """Collective transport: gather every source's (wire bytes, scales).

    One XLA all-gather per exchange axis (right-to-left), so the flattened
    leading axis is row-major over ``axis_names`` — the same linearization
    the (G,)-stacked simulator uses for its group index. The gather is the
    invariant kind: every endpoint holds the same canonical stack, so the
    reduction over it is typed replicated for the ``P()`` outer-state
    ``out_specs``. Runs inside ``shard_map`` and under
    ``vmap(axis_name=...)`` (the single-device test harness). Returns
    ``((E, nw) wire, (E, nb) scales)`` with E = Π sizes.
    """
    names = tuple(axis_names)
    _check_axis_sizes(names, axis_sizes)
    wg, sg = w[None], s[None]
    for ax in reversed(names):
        wg = compat.all_gather_invariant(wg, ax)
        sg = compat.all_gather_invariant(sg, ax)
    return (wg.reshape(-1, w.shape[0]), sg.reshape(-1, s.shape[0]))


def _linear_exchange_idx(axis_names, axis_sizes, axis_coords):
    """(E, linearized row-major index) over the exchange axes."""
    E, idx = 1, jnp.int32(0)
    for ax in axis_names:
        E *= int(axis_sizes[ax])
        idx = idx * int(axis_sizes[ax]) + _axis_idx(ax, axis_coords)
    return E, idx


def _ring_scatter(slots: jax.Array, axis_name: str, size: int,
                  idx) -> jax.Array:
    """Direct shard exchange: (E, ·) per-slot buffers -> (E, ·) stack of
    *my* slot as held by every source, in canonical source order.

    At offset ``k`` every device sends slot ``(idx + k) % E`` straight to
    its owner (``ppermute`` with the stride-k permutation — one slot per
    link per step), so the receiver at distance k deposits the arriving
    buffer — the sender's copy of *the receiver's* slot — into the
    sender's canonical row. Per-device traffic over E−1 offsets is
    ``(E−1)/E`` of the payload: the reduce-scatter byte win, not a
    gather of everything.
    """
    out = jnp.zeros((size, *slots.shape[1:]), slots.dtype)
    own = jax.lax.dynamic_index_in_dim(slots, idx, 0, keepdims=False)
    out = jax.lax.dynamic_update_index_in_dim(out, own, idx, 0)
    for k in range(1, size):
        perm = [(i, (i + k) % size) for i in range(size)]
        buf = jax.lax.dynamic_index_in_dim(slots, (idx + k) % size, 0,
                                           keepdims=False)
        buf = jax.lax.ppermute(buf, axis_name, perm)
        out = jax.lax.dynamic_update_index_in_dim(out, buf,
                                                  (idx - k) % size, 0)
    return out


def ring_scatter_wire(w_slots: jax.Array, s_slots: jax.Array,
                      axis_names: Sequence[str],
                      axis_sizes: Mapping[str, int],
                      axis_coords=None) -> Tuple[jax.Array, jax.Array]:
    """ppermute reduce-scatter transport: my shard slot from every source.

    ``w_slots``/``s_slots``: (E, ·) per-slot packed wire buffers
    (:func:`repro.kernels.ref.shard_slot_wire`). A single exchange axis
    runs the direct stride-k shard exchange ((E−1)/E·payload per
    device); composed axes fall back to the nested-ring full gather +
    slice (correct, but gather-sized traffic — the multi-axis rs case
    has no single ring to stride over).
    """
    names = tuple(axis_names)
    _check_axis_sizes(names, axis_sizes)
    E, idx = _linear_exchange_idx(names, axis_sizes, axis_coords)
    if len(names) == 1:
        wg = _ring_scatter(w_slots, names[0], E, idx)
        sg = _ring_scatter(s_slots, names[0], E, idx)
        return wg, sg
    wg_all, sg_all = ring_gather_wire(
        w_slots.reshape(-1), s_slots.reshape(-1), names, axis_sizes)
    wg = jax.lax.dynamic_index_in_dim(
        wg_all.reshape(E, *w_slots.shape), idx, 1, keepdims=False)
    sg = jax.lax.dynamic_index_in_dim(
        sg_all.reshape(E, *s_slots.shape), idx, 1, keepdims=False)
    return wg, sg


def onehot_scatter_wire(w_slots: jax.Array, s_slots: jax.Array,
                        axis_names: Sequence[str],
                        axis_sizes: Mapping[str, int],
                        axis_coords=None) -> Tuple[jax.Array, jax.Array]:
    """psum reduce-scatter transport (a cross-check of the ring lane).

    Deposits the per-slot stack at the canonical source row of a zero
    (E, E, ·) cube and psums — every endpoint then slices the column of
    its own slot index. Exact (one contributor per cell) and lowerable
    where ppermute CHECK-fails; the byte win of a true reduce-scatter
    lives in the ring/dma transports — this is the correctness lane.
    """
    names = tuple(axis_names)
    _check_axis_sizes(names, axis_sizes)
    E, idx = _linear_exchange_idx(names, axis_sizes, axis_coords)

    def scatter(slots):
        buf = jnp.zeros((E, *slots.shape), slots.dtype)
        buf = jax.lax.dynamic_update_index_in_dim(buf, slots, idx, 0)
        cube = jax.lax.psum(buf, names)  # (E_src, E_slot, ·)
        return jax.lax.dynamic_index_in_dim(cube, idx, 1, keepdims=False)

    return scatter(w_slots), scatter(s_slots)


def onehot_gather_wire(w: jax.Array, s: jax.Array,
                       axis_names: Sequence[str],
                       axis_sizes: Mapping[str, int],
                       axis_coords=None) -> Tuple[jax.Array, jax.Array]:
    """psum transport: scatter into the canonical slot, sum the slots.

    Every endpoint deposits its payload at its linearized index of an
    all-zero ``(E, ...)`` buffer and psums over the exchange axes — each
    slot has exactly one non-zero contributor, so the gather is exact for
    the int values and the (non-negative) fp32 scales in any reduction
    order — a cross-check of the all-gather, at E× its bytes.
    """
    names = tuple(axis_names)
    _check_axis_sizes(names, axis_sizes)
    E, idx = 1, jnp.int32(0)
    for ax in names:
        E *= int(axis_sizes[ax])
        idx = idx * int(axis_sizes[ax]) + _axis_idx(ax, axis_coords)

    def gather(x):
        buf = jnp.zeros((E, *x.shape), x.dtype)
        buf = jax.lax.dynamic_update_index_in_dim(buf, x, idx, 0)
        return jax.lax.psum(buf, names)

    return gather(w), gather(s)


# ---------------------------------------------------------------------------
# Pallas remote-DMA transport (real TPU rings only)
# ---------------------------------------------------------------------------

# Barrier-semaphore ids for the DMA rings, unique among concurrently-live
# collectives in a traced program (ids are assigned at trace time; the
# modulus keeps them inside Mosaic's small-id space — a collision needs
# ~1024 in-flight collectives, far beyond any real leaf count).
_collective_ids = itertools.count()


def _next_collective_id() -> int:
    return next(_collective_ids) % 1024


def _ring_allgather_kernel(x_ref, out_ref, comm_buf, send_sem, recv_sem, *,
                           num_devices: int, axis_name: str):
    """Store-and-forward ring all-gather of one buffer (guide pattern).

    Every device forwards the slot it just received to its right neighbor;
    after E−1 hops ``out_ref`` holds all sources in canonical slots. The
    neighbor barrier keeps a fast device from issuing into a slot its
    neighbor has not drained yet.
    """
    my = jax.lax.axis_index(axis_name)
    left = jax.lax.rem(my + num_devices - 1, num_devices)
    right = jax.lax.rem(my + 1, num_devices)

    out_ref[my] = x_ref[...]
    comm_buf[0] = x_ref[...]

    barrier = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(barrier, inc=1, device_id={axis_name: left})
    pltpu.semaphore_signal(barrier, inc=1, device_id={axis_name: right})
    pltpu.semaphore_wait(barrier, 2)

    for step in range(num_devices - 1):
        slot = step % 2
        nxt = (step + 1) % 2
        rdma = pltpu.make_async_remote_copy(
            src_ref=comm_buf.at[slot],
            dst_ref=comm_buf.at[nxt],
            send_sem=send_sem.at[slot],
            recv_sem=recv_sem.at[nxt],
            # the peer's coordinate along the ring axis; every other mesh
            # coordinate is this device's own
            device_id={axis_name: right},
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        rdma.start()
        rdma.wait()
        src = jax.lax.rem(my + num_devices - step - 1, num_devices)
        out_ref[src] = comm_buf[nxt]


def _ring_allgather_tpu_1d(x: jax.Array, axis_name: str,
                           size: int, collective_id: int) -> jax.Array:
    """(n,) buffer -> (size, n) canonical gather over one mesh axis."""
    (n,) = x.shape
    return pl.pallas_call(
        functools.partial(_ring_allgather_kernel, num_devices=size,
                          axis_name=axis_name),
        # every device ends with the same canonical stack
        out_shape=out_struct((size, n), x.dtype, x,
                             invariant_over=(axis_name,)),
        # whole-array VMEM refs: Mosaic can index these directly, unlike
        # ANY-space refs; _WIRE_CHUNK_BYTES bounds the footprint
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, n), x.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=_CompilerParams(collective_id=collective_id),
        name="ring_allgather",
    )(x)


def ring_allgather_wire_tpu(w: jax.Array, s: jax.Array, axis_name: str,
                            size: int) -> Tuple[jax.Array, jax.Array]:
    """TPU remote-DMA transport: gather wire bytes + scales ring-wise.

    The wire buffer is sliced into ≤ ``_WIRE_CHUNK_BYTES`` panels so the
    double-buffered comm slots fit VMEM regardless of leaf size; scales
    ride as one (small) extra panel. The reduction itself stays in
    :func:`dequant_sum_sources` — this function only moves bytes.
    """
    (nw,) = w.shape
    chunk = max(_WIRE_CHUNK_BYTES // max(w.dtype.itemsize, 1), 1)
    parts = []
    # distinct collective_id per pallas_call, allocated process-wide (not
    # per ring_allgather_wire_tpu call): chunk rings of one leaf AND the
    # rings of different leaves in one outer computation are all
    # data-independent, and any two concurrently-scheduled collectives
    # sharing an id would alias one barrier semaphore and desynchronize
    for lo in range(0, nw, chunk):
        parts.append(mosaic_call(functools.partial(
            _ring_allgather_tpu_1d, axis_name=axis_name, size=size,
            collective_id=_next_collective_id()), w[lo:lo + chunk]))
    wg = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    sg = mosaic_call(functools.partial(
        _ring_allgather_tpu_1d, axis_name=axis_name, size=size,
        collective_id=_next_collective_id()), s)
    return wg, sg


def _shard_scatter_kernel(slots_ref, out_ref, send_buf, recv_buf,
                          send_sem, recv_sem, *, num_devices: int,
                          axis_name: str):
    """Remote-DMA shard exchange: slot ``e`` of every device -> device e.

    At offset ``k`` every device stages its slot ``(my + k) % E`` and
    DMAs it straight to the owner (the stride-k permutation of the ring
    — still a permutation, so the SPMD ``rdma.wait()`` semantics of the
    guide's ring pattern hold: the matching incoming descriptor uses the
    same step-parity semaphore slots on every device). Per-device bytes
    over the E−1 offsets are (E−1)/E of the payload — the reduce-scatter
    win on the real fabric. The opening barrier is *global* (unlike the
    neighbor barrier of the all-gather kernel): sends target arbitrary
    ring distances, so every peer must be inside the kernel before the
    first copy is issued.
    """
    my = jax.lax.axis_index(axis_name)

    own = pl.load(slots_ref, (pl.ds(my, 1), slice(None)))
    pl.store(out_ref, (pl.ds(my, 1), slice(None)), own)

    barrier = pltpu.get_barrier_semaphore()
    for off in range(1, num_devices):
        pltpu.semaphore_signal(
            barrier, inc=1,
            device_id={axis_name: jax.lax.rem(my + off, num_devices)})
    pltpu.semaphore_wait(barrier, num_devices - 1)

    for k in range(1, num_devices):
        dst = jax.lax.rem(my + k, num_devices)
        src = jax.lax.rem(my + num_devices - k, num_devices)
        slot = (k - 1) % 2
        send_buf[slot] = pl.load(slots_ref,
                                 (pl.ds(dst, 1), slice(None)))[0]
        rdma = pltpu.make_async_remote_copy(
            src_ref=send_buf.at[slot],
            dst_ref=recv_buf.at[slot],
            send_sem=send_sem.at[slot],
            recv_sem=recv_sem.at[slot],
            device_id={axis_name: dst},
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        rdma.start()
        rdma.wait()
        pl.store(out_ref, (pl.ds(src, 1), slice(None)),
                 recv_buf[slot][None])


def _shard_scatter_tpu_1d(slots: jax.Array, axis_name: str, size: int,
                          collective_id: int) -> jax.Array:
    """(E, n) per-slot buffers -> (E, n) canonical stack of my slot."""
    _, n = slots.shape
    return pl.pallas_call(
        functools.partial(_shard_scatter_kernel, num_devices=size,
                          axis_name=axis_name),
        out_shape=out_struct((size, n), slots.dtype, slots),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, n), slots.dtype),
            pltpu.VMEM((2, n), slots.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=_CompilerParams(collective_id=collective_id),
        name="shard_scatter",
    )(slots)


def shard_scatter_wire_tpu(w_slots: jax.Array, s_slots: jax.Array,
                           axis_name: str,
                           size: int) -> Tuple[jax.Array, jax.Array]:
    """TPU remote-DMA reduce-scatter transport (chunked like the ring).

    Slot-sized panels are sliced to ≤ ``_WIRE_CHUNK_BYTES`` so the
    staging buffers fit VMEM; scales ride as one extra panel. The
    reduction stays in :func:`dequant_sum_sources` — bytes only here.
    """
    nw = w_slots.shape[1]
    chunk = max(_WIRE_CHUNK_BYTES // max(w_slots.dtype.itemsize, 1), 1)
    parts = []
    for lo in range(0, nw, chunk):
        parts.append(mosaic_call(functools.partial(
            _shard_scatter_tpu_1d, axis_name=axis_name, size=size,
            collective_id=_next_collective_id()), w_slots[:, lo:lo + chunk]))
    wg = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    sg = mosaic_call(functools.partial(
        _shard_scatter_tpu_1d, axis_name=axis_name, size=size,
        collective_id=_next_collective_id()), s_slots)
    return wg, sg


# ---------------------------------------------------------------------------
# public entry: quantized ring all-reduce
# ---------------------------------------------------------------------------


def resolve_transport(*, axis_names: Sequence[str],
                      use_pallas: bool = True) -> str:
    """Backend-aware wire-transport resolution (the ``"auto"`` rule).

    The remote-DMA ring is TPU-only twice over: the resolved kernel
    backend must serve ``ring_allreduce`` compiled (its capability-table
    lane — interpret/jnp-ref force the collective transports even on TPU
    hardware) AND the process must actually run on TPU devices (a forced
    ``tpu-mosaic`` backend on CPU still falls back) — and it only
    composes over a single exchange axis. Everything else resolves to the
    collective ``"ring"`` transport.

    ``use_pallas=True`` (the default) answers "best transport this
    backend could use"; strategies pass their actual ``ReduceCtx``
    setting at dispatch time.
    """
    names = tuple(axis_names)
    if (use_pallas and len(names) == 1 and on_tpu()
            and kernel_lane("ring_allreduce") == COMPILED):
        return "dma"
    return "ring"


def ring_allreduce_quantized(q: jax.Array, s: jax.Array, *,
                             axis_names: Sequence[str],
                             axis_sizes: Mapping[str, int],
                             bits: int, block: int,
                             use_pallas: bool = False,
                             axis_coords=None,
                             transport: str = "auto",
                             weights=None) -> jax.Array:
    """All-reduce the actual (q, scales) pairs over the exchange axes.

    ``q``: (nb·block,) int8 values, ``s``: (nb,) fp32 scales — one
    endpoint's quantized payload. Returns the fp32 (nb·block,) mean of all
    endpoints' dequantized payloads, accumulated in canonical source order
    (bit-identical on every endpoint, whichever transport produced the
    source stack). Must run inside ``shard_map`` (or
    ``vmap(axis_name=...)``) spanning ``axis_names``.

    ``weights``: optional (E,) fp32 participation weights in the same
    canonical source order as the gathered stack (row-major over
    ``axis_names``); forwarded to :func:`dequant_sum_sources` for the
    elastic-membership weighted mean (DESIGN.md §11). Every endpoint must
    pass the identical vector — it is replicated, not per-shard.

    ``transport``: ``"dma"`` (Pallas remote-DMA ring, real TPU only),
    ``"ring"`` (ppermute hops), ``"psum"`` (one-hot scatter + psum), or
    ``"auto"`` — resolved backend-aware by :func:`resolve_transport`.
    """
    names = tuple(axis_names)
    w = pack_wire(q, bits)
    if transport == "auto":
        transport = resolve_transport(axis_names=names,
                                      use_pallas=use_pallas)
    if transport == "dma":
        _check_axis_sizes(names[:1], axis_sizes)
        wg, sg = ring_allgather_wire_tpu(
            w, s, names[0], axis_sizes[names[0]])
    elif transport == "ring":
        wg, sg = ring_gather_wire(w, s, names, axis_sizes)
    elif transport == "psum":
        wg, sg = onehot_gather_wire(w, s, names, axis_sizes, axis_coords)
    else:
        raise ValueError(f"unknown wire transport {transport!r}")
    return dequant_sum_sources(wg, sg, bits=bits, block=block,
                               weights=weights)


def reduce_scatter_qs(q: jax.Array, s: jax.Array, *,
                      axis_names: Sequence[str],
                      axis_sizes: Mapping[str, int],
                      bits: int, block: int,
                      use_pallas: bool = False,
                      axis_coords=None,
                      transport: str = "auto",
                      weights=None) -> jax.Array:
    """Quantized reduce-scatter: each endpoint gets its reduced 1/E shard.

    ``q``: (nb·block,) int8 values, ``s``: (nb,) fp32 scales — one
    endpoint's quantized payload. The payload is split into E fixed-size
    slots of ``wire_shard_blocks(nb, E)`` quant blocks (zero-padded at the
    tail; zero blocks quantize to zero scales and dequantize to exact
    zeros, so padding is bit-transparent), each slot packed independently
    so int4 nibbles never straddle slot boundaries. Endpoint ``e``
    receives slot ``e`` of every source and reduces through the shared
    :func:`dequant_sum_sources` oracle — returning the fp32
    (sb·block,) mean of its own shard, bit-identical to rows of
    :func:`repro.kernels.ref.reduce_scatter_qs_ref`.

    Per-device wire traffic on the ring/dma transports is
    (E−1)/E·payload — the reduce-scatter win. The psum transport is the
    correctness cross-check (gather-sized traffic).
    """
    names = tuple(axis_names)
    E = 1
    for ax in names:
        E *= int(axis_sizes[ax])
    w_slots, s_slots = shard_slot_wire(q, s, bits=bits, block=block,
                                       endpoints=E)
    if transport == "auto":
        transport = resolve_transport(axis_names=names,
                                      use_pallas=use_pallas)
    if transport == "dma":
        _check_axis_sizes(names[:1], axis_sizes)
        wg, sg = shard_scatter_wire_tpu(
            w_slots, s_slots, names[0], axis_sizes[names[0]])
    elif transport == "ring":
        wg, sg = ring_scatter_wire(w_slots, s_slots, names, axis_sizes,
                                   axis_coords)
    elif transport == "psum":
        wg, sg = onehot_scatter_wire(w_slots, s_slots, names, axis_sizes,
                                     axis_coords)
    else:
        raise ValueError(f"unknown wire transport {transport!r}")
    return dequant_sum_sources(wg, sg, bits=bits, block=block,
                               weights=weights)


def allgather_qs(q2: jax.Array, s2: jax.Array, *,
                 axis_names: Sequence[str],
                 axis_sizes: Mapping[str, int],
                 bits: int, block: int,
                 use_pallas: bool = False,
                 axis_coords=None,
                 transport: str = "auto") -> jax.Array:
    """Quantized all-gather: reconstruct the full payload from shards.

    ``q2``: (sb·block,) int8 re-quantized reduced shard, ``s2``: (sb,)
    fp32 scales — endpoint ``e`` holds shard ``e``. Ships the packed
    (w2, s2) pair over the same three transports as the all-reduce wire
    path and concatenates per-slot dequantizations in canonical source
    order via :func:`dequant_concat_sources` — every endpoint
    reconstructs the identical (E·sb·block,) fp32 payload (concatenation,
    not summation: no FMA-order hazard, bit-identical everywhere).
    """
    names = tuple(axis_names)
    w2 = pack_wire(q2, bits)
    if transport == "auto":
        transport = resolve_transport(axis_names=names,
                                      use_pallas=use_pallas)
    if transport == "dma":
        _check_axis_sizes(names[:1], axis_sizes)
        wg, sg = ring_allgather_wire_tpu(
            w2, s2, names[0], axis_sizes[names[0]])
    elif transport == "ring":
        wg, sg = ring_gather_wire(w2, s2, names, axis_sizes)
    elif transport == "psum":
        wg, sg = onehot_gather_wire(w2, s2, names, axis_sizes, axis_coords)
    else:
        raise ValueError(f"unknown wire transport {transport!r}")
    return dequant_concat_sources(wg, sg, bits=bits, block=block)


# ---------------------------------------------------------------------------
# measured bytes-on-wire (benchmarks/overlap.py --json)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=32)
def _measure_wire_sample(sample: int, bits: int, block: int):
    """(value_bytes, scale_bytes) of a real quantize+pack of ``sample``
    elements — cached: the sweep and the sync_delay='auto' startup path
    ask for the same (sample, bits, block) repeatedly, and the underlying
    jax work is identical each time."""
    from repro.kernels.ref import quantize_blockwise_ref

    x = jnp.zeros((sample,), jnp.float32)
    if bits >= 32:
        return int(x.nbytes), 0  # fp32 ships uncompressed, no scales
    q, s = quantize_blockwise_ref(x, bits=bits, block=block)
    return int(pack_wire(q, bits).nbytes), int(s.nbytes)


def measure_wire_bytes(n: int, *, bits: int = 8, block: int = 256,
                       sample_cap: int = 1 << 22) -> dict:
    """Measured wire bytes for an n-element payload: run the real
    quantizer + packer and read ``.nbytes`` off the actual buffers.

    Payloads above ``sample_cap`` elements are measured on a cap-sized
    sample and scaled (the per-element layout — block padding, scale rows,
    nibble packing — is what measurement captures; it is size-invariant
    beyond one block row). Returns per-payload totals and the measured
    bytes-per-element, for comparison against the ``bits/8 + 4/block``
    model.
    """
    sample = int(min(n, sample_cap))
    value_bytes, scale_bytes = _measure_wire_sample(sample, bits, block)
    per_elem = (value_bytes + scale_bytes) / max(sample, 1)
    total = per_elem * n
    return {
        "measured_sample_elems": sample,
        "measured_value_bytes": value_bytes,
        "measured_scale_bytes": scale_bytes,
        "measured_payload_bytes_per_param": per_elem,
        "measured_payload_bytes": total,
    }


def measured_cross_domain_bytes(n: int, *, endpoints: int, bits: int = 8,
                                block: int = 256) -> float:
    """Measured total bytes crossing the slow domain per sync, using the
    same ring-traffic convention as the analytic model (2·P·(E−1)) but
    with the *measured* per-payload bytes."""
    per = measure_wire_bytes(n, bits=bits, block=block)
    return 2.0 * per["measured_payload_bytes"] * (max(endpoints, 1) - 1)


@functools.lru_cache(maxsize=32)
def _measure_slot_sample(sample: int, endpoints: int, bits: int,
                         block: int):
    """(slot_value_bytes, slot_scale_bytes) of one real rs/ag slot for a
    ``sample``-element payload: run the actual quantize + per-slot pack
    and read ``.nbytes`` off the slot buffers (captures block padding,
    slot zero-padding, and per-slot nibble packing exactly)."""
    from repro.kernels.ref import quantize_blockwise_ref

    x = jnp.zeros((sample,), jnp.float32)
    q, s = quantize_blockwise_ref(x, bits=bits, block=block)
    w_slots, s_slots = shard_slot_wire(q, s, bits=bits, block=block,
                                      endpoints=endpoints)
    return int(w_slots[0].nbytes), int(s_slots[0].nbytes)


def measured_rs_ag_bytes(n: int, *, endpoints: int, bits: int = 8,
                         block: int = 256,
                         sample_cap: int = 1 << 22) -> dict:
    """Measured per-device wire bytes for the rs/ag exchange.

    Convention: bytes *sent* per device per sync. Each device sends
    (E−1) quantized payload slots on the reduce-scatter leg and its one
    re-quantized (q2, s2) slot to (E−1) peers on the all-gather leg —
    2·(E−1)·slot_bytes total, vs (E−1)·payload_bytes for the
    gather-based all-reduce wire path (ratio 2/E: 0.5× at E=4). Slot
    sizes come from real buffers (see :func:`_measure_slot_sample`);
    payloads above ``sample_cap`` are measured on a sample and scaled.
    """
    E = max(int(endpoints), 1)
    sample = int(min(n, sample_cap))
    value_bytes, scale_bytes = _measure_slot_sample(sample, E, bits, block)
    scale = n / max(sample, 1)
    slot_bytes = (value_bytes + scale_bytes) * scale
    per_leg = (E - 1) * slot_bytes
    return {
        "measured_slot_bytes": slot_bytes,
        "measured_rs_bytes_per_device": per_leg,
        "measured_ag_bytes_per_device": per_leg,
        "measured_rs_ag_bytes_per_device": 2.0 * per_leg,
        "measured_rs_ag_bytes_total": 2.0 * per_leg * E,
    }
