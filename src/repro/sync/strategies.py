"""Concrete outer-sync strategies + the legacy-flag resolver (DESIGN.md §7).

- :class:`FlatFP32` — the seed collective: one flat fp32 pmean of Δθ over
  every manual (group) axis. Bit-identical to the pre-strategy path.
- :class:`Quantized` — blockwise-quantized payload (int8/int4 values +
  per-block fp32 absmax scales) with an error-feedback residual carried
  group-locally in ``OuterState.residual``. The *dequantized* value is
  exchanged — the numeric model of the wire format at fp32 wire width.
- :class:`Int8Wire` — the true wire format (DESIGN.md §8): the actual
  packed ``(q, scales)`` pairs cross the slow exchange axes through a
  ring exchange (Pallas remote-DMA on TPU, ``ppermute`` reference
  elsewhere) and are reduced with per-source-scale sum semantics —
  numerically the same payload mean as :class:`Quantized`, with the bytes
  win real instead of accounted.
- :class:`Sharded` — auto-axis combinator (DESIGN.md §10): each device
  compresses and exchanges only its Δθ *shard* along the auto (GSPMD)
  axes — the per-leaf ``PartitionSpec`` threaded through
  ``ReduceCtx.leaf_spec`` — so the outer exchange (and, with
  ``sharded_state``, the outer momentum/anchor/residual) stops scaling
  with full model size. fp32 inner stays bit-identical to the replicated
  path; quantized inner is block-content-identical to :class:`Quantized`.
- :class:`Hierarchical` — two-stage combinator: full-precision mean over
  the fast intra-pod axes first, then the *inner* strategy's exchange over
  the slow pod axes (1/pods of the traffic crosses the slow domain).
- :class:`Chunked` — span combinator: the Δθ leaf tree dispatches as
  ``num_chunks`` contiguous spans, each its own XLA computation with its
  own per-chunk :class:`~repro.sync.base.ChunkDispatch`, so early chunks'
  collectives (and applies) overlap later chunks' quantization.

:func:`resolve_strategy` maps an :class:`~repro.config.OuterCommConfig`
(or a ``TrainConfig`` carrying one — including every legacy flat-flag
combination via the deprecation shim) onto the equivalent strategy object.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro import compat
from repro.core.outer import compress_delta, outer_reduce
from repro.sync.base import (OuterSyncStrategy, ReduceCtx, SyncPlan,
                             balanced_spans, constrain_to_spec, _leaf_sizes,
                             weighted_psum_mean, weighted_stack_mean)


def _lone_endpoint(payload, ctx: ReduceCtx):
    """The delivered payload when the exchange has a single endpoint.

    It is the mean already; a psum over the size-1 exchange axes adds
    nothing and types it replicated, as the ``P()`` outer-state
    ``out_specs`` require (a wire exchange over E > 1 gets that from its
    invariant gather).
    """
    if not ctx.exchange_axes:
        return payload
    return jax.lax.psum(payload, ctx.exchange_axes)


def _from_first(x, ctx: ReduceCtx):
    """``x`` of the device at fast-axes coordinate 0, on every device.

    A psum with one non-zero contributor: exact, and typed invariant over
    ``ctx.fast_axes``.
    """
    from repro.kernels.ring_allreduce import _linear_exchange_idx

    _, idx = _linear_exchange_idx(ctx.fast_axes, ctx.axis_sizes,
                                  ctx.axis_coords)
    return jax.lax.psum(jnp.where(idx == 0, x, jnp.zeros_like(x)),
                        ctx.fast_axes)


@dataclass(frozen=True)
class FlatFP32(OuterSyncStrategy):
    """Flat fp32 pmean of Δθ over the manual axes — the seed collective."""

    @property
    def name(self) -> str:
        return "flat-fp32"

    def reduce_leaf(self, d, r, tc, ctx: ReduceCtx):
        if ctx.exchange_axes:
            if ctx.weight is not None:
                d = weighted_psum_mean(d, ctx.weight, ctx.exchange_axes)
            else:
                d = jax.lax.pmean(d, ctx.exchange_axes)
        return d, r

    def sim_dispatch(self, group_params, outer, tc, *, mu, lr, num_pods=1,
                     weights=None):
        # Mean the replicas BEFORE subtracting the anchor — the seed
        # simulator's operation order, preserved bit for bit (mean-then-
        # subtract and subtract-then-mean agree mathematically, not in
        # floating point).
        if weights is None:
            mean_params = jax.tree.map(
                lambda p: jnp.mean(p.astype(jnp.float32), axis=0),
                group_params)
        else:
            mean_params = jax.tree.map(
                lambda p: weighted_stack_mean(p.astype(jnp.float32),
                                              weights), group_params)
        delta = jax.tree.map(
            lambda m, a: m - a.astype(jnp.float32), mean_params, outer.anchor)
        return outer_reduce(outer, delta, tc, mu=mu, lr=lr)

    def sim_reduce(self, delta, residual, tc, *, num_pods=1,
                   pod_grouped=False, weights=None):
        if weights is None:
            return jax.tree.map(lambda d: jnp.mean(d, axis=0),
                                delta), residual
        return jax.tree.map(lambda d: weighted_stack_mean(d, weights),
                            delta), residual


@dataclass(frozen=True)
class Quantized(OuterSyncStrategy):
    """Blockwise-quantized Δθ payload with error feedback.

    Each group (or pod, under :class:`Hierarchical`) quantizes its payload
    to ``bits`` with per-``block`` fp32 absmax scales; the *dequantized*
    value — exactly what int8+scales deliver on the wire — is exchanged,
    and what quantization dropped is carried in the residual so the error
    telescopes across syncs instead of biasing the Nesterov momentum.
    """

    bits: int = 8
    block: int = 256

    needs_residual = True

    @property
    def name(self) -> str:
        return f"quantized(int{self.bits},block={self.block})"

    def reduce_leaf(self, d, r, tc, ctx: ReduceCtx):
        d, r = compress_delta(d, r, bits=self.bits, block=self.block,
                              use_pallas=ctx.use_pallas)
        if ctx.exchange_axes:
            if ctx.weight is not None:
                d = weighted_psum_mean(d, ctx.weight, ctx.exchange_axes)
            else:
                d = jax.lax.pmean(d, ctx.exchange_axes)
        return d, r

    def sim_reduce(self, delta, residual, tc, *, num_pods=1,
                   pod_grouped=False, weights=None):
        payload, new_res = jax.vmap(
            lambda d, r: compress_delta(d, r, bits=self.bits,
                                        block=self.block))(delta, residual)
        if weights is None:
            return jax.tree.map(lambda d: jnp.mean(d, axis=0),
                                payload), new_res
        return jax.tree.map(lambda d: weighted_stack_mean(d, weights),
                            payload), new_res


@dataclass(frozen=True)
class Int8Wire(OuterSyncStrategy):
    """True int8 wire format: ring exchange of the packed (q, scales) pairs.

    Same blockwise quantization + error feedback as :class:`Quantized`,
    but the *actual* quantized payload crosses the slow exchange axes —
    packed int8 (or nibble-packed int4) values plus per-block fp32 absmax
    scales — through a store-and-forward ring (Pallas remote-DMA on a real
    TPU, a ``jax.lax.ppermute`` reference ring elsewhere). Each endpoint
    accumulates the per-source dequantized partials in canonical source
    order and multiplies by ``1/E`` (per-source-scale sum semantics,
    DESIGN.md §8), so every endpoint produces bit-identical results and
    the payload mean equals :class:`Quantized`'s dequantized-payload mean.

    ``reduce_scatter=True`` replaces the full-payload ring with the
    explicit reduce-scatter → all-gather wire path (DESIGN.md §14):
    endpoint e reduces only slot e of every source's payload
    (``kernels.ring_allreduce.reduce_scatter_qs``), re-quantizes its
    reduced 1/E shard behind a *second* error-feedback residual
    (``OuterState.residual2``), and all-gathers the packed ``(q2, s2)``
    pair (``allgather_qs``) — per-device sent bytes drop from
    (E−1)·payload to 2·(E−1)/E·payload, and the residual/payload pair
    still telescopes exactly: ``reduced + r2 == dequant(q2, s2) + r2'``
    per slot. Both residuals thread as an opaque ``(r1, r2)`` pair (see
    ``OuterSyncStrategy.needs_residual2``).
    """

    bits: int = 8
    block: int = 256
    reduce_scatter: bool = False

    needs_residual = True

    @property
    def needs_residual2(self) -> bool:  # type: ignore[override]
        return self.reduce_scatter

    @property
    def name(self) -> str:
        if self.reduce_scatter:
            return f"rs-ag(int{self.bits},block={self.block})"
        return f"int{self.bits}-wire(block={self.block})"

    @property
    def wire_format(self) -> str:  # type: ignore[override]
        if self.reduce_scatter:
            return f"int{self.bits}+scales/rs-ag"
        return f"int{self.bits}+scales"

    def wire_bytes_per_param(self, tc) -> float:
        return self.bits / 8.0 + 4.0 / self.block

    def transport_name(self, mesh=None) -> str:
        from repro.kernels.ring_allreduce import resolve_transport

        names = ("data_outer",)
        if mesh is not None:
            from repro.launch.mesh import manual_axes

            names = manual_axes(mesh) or names
        return resolve_transport(axis_names=names)

    def reduce_leaf(self, d, r, tc, ctx: ReduceCtx):
        from repro.core.outer import quant_fns
        from repro.kernels.ring_allreduce import ring_allreduce_quantized

        if self.reduce_scatter:
            return self._reduce_leaf_rs_ag(d, r, tc, ctx)
        quant, dequant = quant_fns(bits=self.bits, block=self.block,
                                   use_pallas=ctx.use_pallas)
        c = d.astype(jnp.float32)
        if r is not None:
            c = c + r.astype(jnp.float32)
        flat = c.reshape(-1)
        n = flat.shape[0]
        q, s = quant(flat)
        # the locally dequantized payload: exactly what every other
        # endpoint reconstructs from our (q, s) on the wire — the error
        # feedback telescopes against the value the wire delivers
        payload_local = dequant(q, s)[:n].reshape(c.shape)
        new_r = c - payload_local
        if not ctx.exchange_axes or ctx.exchange_size() <= 1:
            return _lone_endpoint(payload_local, ctx), new_r
        # ctx.weights rides in exchange order (row-major over the
        # exchange axes — pod-level under Hierarchical, which narrows
        # the ctx with pod weight sums); None keeps the 1/E sum.
        avg = ring_allreduce_quantized(
            q, s, axis_names=ctx.exchange_axes, axis_sizes=ctx.axis_sizes,
            bits=self.bits, block=self.block, use_pallas=ctx.use_pallas,
            axis_coords=ctx.axis_coords, weights=ctx.weights)
        return avg[:n].reshape(c.shape), new_r

    def _reduce_leaf_rs_ag(self, d, r, tc, ctx: ReduceCtx):
        """The reduce-scatter + all-gather exchange of one Δθ leaf.

        ``r`` arrives as the opaque ``(r1, r2)`` residual pair (or None on
        the stateless path). The second residual is *stored* full-size in
        the leaf's shape — zeros outside this endpoint's own slot — so
        the OuterState layout (and its sharding specs) stay uniform; the
        slot is sliced out/scattered back around the exchange. Slot
        padding positions beyond the leaf carry exact zeros end to end
        (zero-padded blocks reduce to zero, a zero residual re-quantizes
        to zero), so truncating the stored residual to the leaf is
        lossless — the invariant tests/test_rs_ag_wire.py proves.
        """
        from repro.core.outer import quant_fns
        from repro.kernels.ref import wire_shard_blocks
        from repro.kernels.ring_allreduce import (_linear_exchange_idx,
                                                  allgather_qs,
                                                  reduce_scatter_qs)

        r1, r2 = r if isinstance(r, tuple) else (r, None)
        quant, dequant = quant_fns(bits=self.bits, block=self.block,
                                   use_pallas=ctx.use_pallas)
        c = d.astype(jnp.float32)
        if r1 is not None:
            c = c + r1.astype(jnp.float32)
        flat = c.reshape(-1)
        n = flat.shape[0]
        q, s = quant(flat)
        payload_local = dequant(q, s)[:n].reshape(c.shape)
        new_r1 = c - payload_local
        if not ctx.exchange_axes or ctx.exchange_size() <= 1:
            # no exchange: deliver the local dequant; the gather-leg
            # residual has nothing new to absorb
            return _lone_endpoint(payload_local, ctx), (new_r1, r2)
        E = ctx.exchange_size()
        sb = wire_shard_blocks(int(s.shape[0]), E)
        slot = sb * self.block
        _, idx = _linear_exchange_idx(ctx.exchange_axes, ctx.axis_sizes,
                                      ctx.axis_coords)
        reduced = reduce_scatter_qs(
            q, s, axis_names=ctx.exchange_axes, axis_sizes=ctx.axis_sizes,
            bits=self.bits, block=self.block, use_pallas=ctx.use_pallas,
            axis_coords=ctx.axis_coords, weights=ctx.weights)
        # second error feedback on my reduced shard, then the gather leg
        if r2 is None:
            r2_shard = jnp.zeros((slot,), jnp.float32)
        else:
            r2_flat = jnp.pad(r2.astype(jnp.float32).reshape(-1),
                              (0, E * slot - n))
            r2_shard = jax.lax.dynamic_slice(r2_flat, (idx * slot,), (slot,))
        c2 = reduced + r2_shard
        q2, s2 = quant(c2)
        new_r2_shard = c2 - dequant(q2, s2)[:slot]
        payload = allgather_qs(
            q2, s2, axis_names=ctx.exchange_axes, axis_sizes=ctx.axis_sizes,
            bits=self.bits, block=self.block, use_pallas=ctx.use_pallas,
            axis_coords=ctx.axis_coords)
        new_r2 = jax.lax.dynamic_update_slice(
            jnp.zeros((E * slot,), jnp.float32), new_r2_shard,
            (idx * slot,))[:n].reshape(c.shape)
        return payload[:n].reshape(c.shape), (new_r1, new_r2)

    def sim_reduce(self, delta, residual, tc, *, num_pods=1,
                   pod_grouped=False, weights=None):
        """Exact model of the ring: per-source-scale sum in source order.

        Shares :func:`repro.kernels.ref.dequant_sum_sources` with the
        distributed transport and the test oracle — the same subgraph on
        the same packed stacks, so the sim ↔ distributed equivalence
        binds bit for bit (not just numerically). ``pod_grouped`` (set by
        the hierarchical combinator) marks the stacked entries as
        pod-duplicated: the ring endpoints are then the pods, one
        representative each — including the pod-less ``P == 1`` case,
        where the distributed path quantizes the global mean once with no
        exchange at all.
        """
        from repro.kernels.ref import (dequant_sum_sources, pack_wire,
                                       dequantize_blockwise_ref,
                                       quantize_blockwise_ref)

        if self.reduce_scatter:
            return self._sim_reduce_rs_ag(delta, residual, tc,
                                          num_pods=num_pods,
                                          pod_grouped=pod_grouped,
                                          weights=weights)
        bits, block = self.bits, self.block
        src_w = weights
        if weights is not None and pod_grouped:
            # pod-duplicated stack: the ring endpoints are the pods, so
            # the per-source weights are the per-entry pod weights'
            # representatives (Hierarchical already broadcast each pod's
            # weight sum over its entries)
            P = max(num_pods, 1)
            src_w = jnp.asarray(weights, jnp.float32).reshape(P, -1)[:, 0]

        def leaf(d, r):
            G = d.shape[0]
            c = d.astype(jnp.float32)
            if r is not None:
                c = c + r.astype(jnp.float32)
            flat = c.reshape(G, -1)
            n = flat.shape[1]
            q, s = jax.vmap(lambda x: quantize_blockwise_ref(
                x, bits=bits, block=block))(flat)
            payload_local = jax.vmap(lambda q1, s1: dequantize_blockwise_ref(
                q1, s1, block=block))(q, s)[:, :n].reshape(c.shape)
            new_r = c - payload_local
            if pod_grouped:
                P = max(num_pods, 1)
                q = q.reshape(P, G // P, *q.shape[1:])[:, 0]
                s = s.reshape(P, G // P, *s.shape[1:])[:, 0]
            E = q.shape[0]
            wg = jnp.stack([pack_wire(q[j], bits) for j in range(E)])
            avg = dequant_sum_sources(wg, s, bits=bits, block=block,
                                      weights=src_w)
            return avg[:n].reshape(c.shape[1:]), new_r

        flat_d, treedef = jax.tree_util.tree_flatten(delta)
        flat_r = (treedef.flatten_up_to(residual) if residual is not None
                  else [None] * len(flat_d))
        out = [leaf(d, r) for d, r in zip(flat_d, flat_r)]
        unf = jax.tree_util.tree_unflatten
        return (unf(treedef, [p for p, _ in out]),
                unf(treedef, [r for _, r in out]))

    def _sim_reduce_rs_ag(self, delta, residual, tc, *, num_pods=1,
                          pod_grouped=False, weights=None):
        """Exact model of the rs/ag exchange: the (G,)-stacked sources
        ARE the endpoints, and the whole round trip runs through
        :func:`repro.kernels.ref.rs_ag_qs_ref` — the identical subgraph
        the distributed ``reduce_scatter_qs``/``allgather_qs`` legs
        decompose into, so sim ↔ distributed binds bit for bit.
        ``residual`` is the opaque ``(r1_tree, r2_tree)`` pair."""
        from repro.kernels.ref import (dequantize_blockwise_ref,
                                       quantize_blockwise_ref,
                                       rs_ag_qs_ref, wire_shard_blocks)

        if pod_grouped:
            raise ValueError(
                "the rs/ag wire path does not compose with the "
                "hierarchical two-stage reduce: the reduce-scatter "
                "already owns the slow-axis layout")
        bits, block = self.bits, self.block
        r1_tree, r2_tree = (residual if isinstance(residual, tuple)
                            else (residual, None))

        def leaf(d, r1, r2):
            G = d.shape[0]
            c = d.astype(jnp.float32)
            if r1 is not None:
                c = c + r1.astype(jnp.float32)
            flat = c.reshape(G, -1)
            n = flat.shape[1]
            q, s = jax.vmap(lambda x: quantize_blockwise_ref(
                x, bits=bits, block=block))(flat)
            payload_local = jax.vmap(lambda q1, s1: dequantize_blockwise_ref(
                q1, s1, block=block))(q, s)[:, :n].reshape(c.shape)
            new_r1 = c - payload_local
            E = G
            if E <= 1:
                return payload_local[0], new_r1, (r2 if r2 is not None
                                                  else jnp.zeros_like(c))
            sb = wire_shard_blocks(int(s.shape[1]), E)
            slot = sb * block
            # endpoint g's stored full-size residual2 -> its own slot g
            if r2 is None:
                r2_shards = jnp.zeros((E, slot), jnp.float32)
            else:
                r2_pad = jnp.pad(r2.astype(jnp.float32).reshape(G, -1),
                                 ((0, 0), (0, E * slot - n)))
                r2_shards = r2_pad.reshape(E, E, slot)[
                    jnp.arange(E), jnp.arange(E)]
            payload, new_r2_shards = rs_ag_qs_ref(
                q, s, block=block, bits=bits, residual2=r2_shards,
                weights=weights)
            new_r2 = jnp.zeros((E, E * slot), jnp.float32).reshape(
                E, E, slot).at[jnp.arange(E), jnp.arange(E)].set(
                new_r2_shards).reshape(E, E * slot)[:, :n].reshape(c.shape)
            return payload[:n].reshape(c.shape[1:]), new_r1, new_r2

        flat_d, treedef = jax.tree_util.tree_flatten(delta)
        flat_r1 = (treedef.flatten_up_to(r1_tree) if r1_tree is not None
                   else [None] * len(flat_d))
        flat_r2 = (treedef.flatten_up_to(r2_tree) if r2_tree is not None
                   else [None] * len(flat_d))
        out = [leaf(d, r1, r2)
               for d, r1, r2 in zip(flat_d, flat_r1, flat_r2)]
        unf = jax.tree_util.tree_unflatten
        return (unf(treedef, [p for p, _, _ in out]),
                (unf(treedef, [r1 for _, r1, _ in out]),
                 unf(treedef, [r2 for _, _, r2 in out])))


@dataclass(frozen=True)
class Sharded(OuterSyncStrategy):
    """Auto-axis combinator: exchange only the per-device Δθ shard.

    The replicated strategies materialize every full Δθ leaf on every
    device before the manual-axis pmean — fine at 124M, fatal at 7B with
    tensor/FSDP parallelism, where no device holds a full leaf to begin
    with. This combinator keeps each leaf pinned to its ``param_specs``
    sharding over the auto (GSPMD) axes — the per-leaf ``PartitionSpec``
    threaded through ``ReduceCtx.leaf_spec`` — so GSPMD lowers the
    manual-axis pmean as shard-local collectives (reduce-scatter +
    all-gather shape, ZeRO++-style) and nothing full-size is ever built.

    - ``Sharded(FlatFP32())``: constraints never change values, and the
      pmean is the same reduction — **bit-identical** to the replicated
      flat-fp32 path.
    - ``Sharded(Quantized(...))``: leaves whose size divides
      ``block * A`` (A = auto-axis shard count) quantize shard-locally —
      every shard holds whole quantization blocks, so blockwise absmax
      never crosses a shard boundary and the blocks are bitwise what the
      unsharded :class:`Quantized` produces. Ragged leaves pad in-graph
      to whole per-shard blocks and still quantize shard-locally. Same
      numeric model, same simulator tolerance.
    - ``Sharded(Int8Wire(...))``: the explicit reduce-scatter +
      all-gather wire exchange (DESIGN.md §14). The combinator force-
      normalizes the inner's ``reduce_scatter=True`` — a full-payload
      ring under the sharded layout would rebuild every leaf on every
      device, the exact thing this combinator exists to avoid — and pins
      the delivered payload and both residuals back to the leaf spec, so
      shard-resident outer state composes with the 1/E wire traffic.

    With ``sharded_state`` the step builder additionally pins the outer
    momentum/anchor/residual(s) and dispatch buffers to the same specs
    via jit ``out_shardings``, so outer-state memory per device scales as
    ~1/(TP×FSDP) (DESIGN.md §10).
    """

    inner: OuterSyncStrategy = FlatFP32()

    sharded_state = True

    def __post_init__(self):
        if isinstance(self.inner, Int8Wire):
            if not self.inner.reduce_scatter:
                # normalize: the sharded wire exchange IS the rs/ag path
                object.__setattr__(
                    self, "inner",
                    dataclasses.replace(self.inner, reduce_scatter=True))
        elif not isinstance(self.inner, (FlatFP32, Quantized)):
            raise ValueError(
                f"Sharded composes FlatFP32, Quantized or Int8Wire, got "
                f"{type(self.inner).__name__}: combinators cannot nest "
                f"inside the sharded exchange")

    @property
    def name(self) -> str:
        return f"sharded[{self.inner.name}]"

    @property
    def needs_residual(self) -> bool:  # type: ignore[override]
        return self.inner.needs_residual

    @property
    def needs_residual2(self) -> bool:  # type: ignore[override]
        return self.inner.needs_residual2

    @property
    def wire_format(self) -> str:  # type: ignore[override]
        return self.inner.wire_format

    def wire_bytes_per_param(self, tc) -> float:
        return self.inner.wire_bytes_per_param(tc)

    def transport_name(self, mesh=None) -> str:
        return self.inner.transport_name(mesh)

    def plan(self, pshapes, tc, mesh=None) -> SyncPlan:
        return self.inner.plan(pshapes, tc, mesh)._replace(name=self.name)

    def reduce_leaf(self, d, r, tc, ctx: ReduceCtx):
        d = constrain_to_spec(d, ctx.leaf_spec, ctx)
        if isinstance(self.inner, Int8Wire):
            # the rs/ag exchange owns reduction AND layout: run it, then
            # pin the delivered payload and both residuals back to the
            # leaf's auto-axis spec so the outer state stays shard-resident
            d, rr = self.inner.reduce_leaf(d, r, tc, ctx)
            d = constrain_to_spec(d, ctx.leaf_spec, ctx)
            if isinstance(rr, tuple):
                rr = tuple(
                    constrain_to_spec(x, ctx.leaf_spec, ctx)
                    if x is not None else None for x in rr)
            elif rr is not None:
                rr = constrain_to_spec(rr, ctx.leaf_spec, ctx)
            return d, rr
        if isinstance(self.inner, Quantized):
            block = self.inner.block
            # ragged leaves pad the flat payload to whole per-shard
            # blocks in-graph and take the shard-local path too
            ragged = d.size % (block * max(ctx.auto_size(), 1)) != 0
            d, r = self._compress_sharded(d, r, ctx, pad=ragged)
        if ctx.exchange_axes:
            if ctx.weight is not None:
                d = weighted_psum_mean(d, ctx.weight, ctx.exchange_axes)
            else:
                d = jax.lax.pmean(d, ctx.exchange_axes)
        d = constrain_to_spec(d, ctx.leaf_spec, ctx)
        return d, r

    def _compress_sharded(self, d, r, ctx: ReduceCtx, *, pad: bool = False):
        """Shard-local blockwise quantize/dequantize with error feedback.

        Works on the flat payload constrained to one combined auto-axis
        dim. Without ``pad`` the caller guarantees the leaf divides into
        whole per-shard blocks (``n % (block·shards) == 0``), so the
        quantize/dequantize round trip never crosses a shard boundary and
        no in-graph pad/slice is needed. With ``pad`` (ragged leaves) the
        flat payload is zero-padded to the next whole per-shard block
        multiple first and the round trip sliced back; zero padding
        quantizes to zero scales and dequantizes to exact zeros, so the
        blocks covering real data are bitwise unchanged.
        """
        from jax.sharding import PartitionSpec as P

        from repro.core.outer import quant_fns

        bits, block = self.inner.bits, self.inner.block
        quant, dequant = quant_fns(bits=bits, block=block,
                                   use_pallas=ctx.use_pallas)
        c = d.astype(jnp.float32)
        if r is not None:
            c = c + r.astype(jnp.float32)
        flat = c.reshape(-1)
        n = flat.shape[0]
        if pad:
            unit = block * max(ctx.auto_size(), 1)
            flat = jnp.pad(flat, (0, -n % unit))
        row = P(tuple(ctx.auto_axes)) if ctx.auto_axes else None
        flat = constrain_to_spec(flat, row, ctx)
        q, s = quant(flat)
        q = constrain_to_spec(q, row, ctx)
        s = constrain_to_spec(s, row, ctx)
        payload = dequant(q, s)
        if pad:  # keep the divisible path's graph byte-identical: no slice
            payload = payload[:n]
        payload = payload.reshape(c.shape)
        payload = constrain_to_spec(payload, ctx.leaf_spec, ctx)
        new_r = constrain_to_spec(c - payload, ctx.leaf_spec, ctx)
        return payload, new_r

    def sim_dispatch(self, group_params, outer, tc, *, mu, lr, num_pods=1,
                     weights=None):
        # the sharded exchange is a layout change, not a numeric one: the
        # simulator models it with the inner strategy's reduction
        return self.inner.sim_dispatch(group_params, outer, tc, mu=mu,
                                       lr=lr, num_pods=num_pods,
                                       weights=weights)

    def sim_reduce(self, delta, residual, tc, *, num_pods=1,
                   pod_grouped=False, weights=None):
        return self.inner.sim_reduce(delta, residual, tc,
                                     num_pods=num_pods,
                                     pod_grouped=pod_grouped,
                                     weights=weights)


@dataclass(frozen=True)
class Hierarchical(OuterSyncStrategy):
    """Two-stage reduce: fp32 intra-pod mean, then ``inner``'s exchange
    over the slow pod axes. Degenerates to ``inner`` over the full manual
    set on a pod-less mesh (where the fast-domain mean is already the full
    reduce)."""

    inner: OuterSyncStrategy = FlatFP32()

    two_stage = True

    def __post_init__(self):
        if getattr(self.inner, "needs_residual2", False):
            raise ValueError(
                "Hierarchical cannot compose the reduce-scatter wire "
                "path: the rs/ag exchange already owns the slow-axis "
                "layout (its shards ARE the endpoints); use the plain "
                "int8-wire ring under Hierarchical, or rs-ag flat / "
                "under Sharded")

    @property
    def name(self) -> str:
        return f"hierarchical[{self.inner.name}]"

    @property
    def needs_residual(self) -> bool:  # type: ignore[override]
        return self.inner.needs_residual

    @property
    def wire_format(self) -> str:  # type: ignore[override]
        return self.inner.wire_format

    @property
    def sharded_state(self) -> bool:  # type: ignore[override]
        return self.inner.sharded_state

    def wire_bytes_per_param(self, tc) -> float:
        return self.inner.wire_bytes_per_param(tc)

    def transport_name(self, mesh=None) -> str:
        return self.inner.transport_name(mesh)

    def reduce_leaf(self, d, r, tc, ctx: ReduceCtx):
        inner_ctx = ctx
        if ctx.fast_axes:
            if ctx.weight is not None:
                # stage 1: weighted fast-domain mean; the pod's weight for
                # stage 2 is its live weight sum (a dead pod exchanges a
                # zero payload at weight 0)
                d = weighted_psum_mean(d, ctx.weight, ctx.fast_axes)
                pod_w = jax.lax.psum(
                    jnp.asarray(ctx.weight, jnp.float32), ctx.fast_axes)
                sizes = ctx.axis_sizes or {}
                P = int(sizes.get("pod", 1))
                # per-pod weight sums in pod (slow-axis) order: manual
                # linearization is pod-major, so the (G,) vector reshapes
                # (P, G//P) directly
                pod_vec = jnp.asarray(ctx.weights, jnp.float32
                                      ).reshape(P, -1).sum(axis=1)
                inner_ctx = ctx.narrowed(ctx.slow_axes).with_membership(
                    pod_vec, pod_w)
            else:
                d = jax.lax.pmean(d, ctx.fast_axes)  # stage 1: fast, fp32
                inner_ctx = ctx.narrowed(ctx.slow_axes)
        d, r = self.inner.reduce_leaf(d, r, tc, inner_ctx)
        if ctx.fast_axes and self.inner.needs_residual:
            # each group compressed the pod mean plus its *own* residual,
            # so the delivered payload is typed varying over the fast
            # axes; every pod delivers its first group's payload (the
            # simulator's pod representative), typed replicated
            d = _from_first(d, ctx)
            if r is not None:
                r = compat.mark_varying(r, ctx.fast_axes)
        return d, r

    def sim_reduce(self, delta, residual, tc, *, num_pods=1,
                   pod_grouped=False, weights=None):
        P = max(num_pods, 1)
        leaves = jax.tree_util.tree_leaves(delta)
        if leaves:
            validate_pod_grouping(leaves[0].shape[0], P)

        # stage 1: full-precision mean over the fast intra-pod axis,
        # broadcast back so every group in a pod holds the pod mean
        # (== its payload input; residuals stay pod-identical). P == 1
        # degenerates to reducing the *global* mean once — exactly the
        # distributed path on a pod-less mesh.
        def pod_mean(d):
            G = d.shape[0]
            dp = d.reshape(P, G // P, *d.shape[1:])
            if weights is not None:
                wp = jnp.asarray(weights, jnp.float32).reshape(
                    (P, G // P) + (1,) * (d.ndim - 1))
                sw = jnp.sum(wp, axis=1, keepdims=True)
                inv = jnp.where(sw > 0, jnp.float32(1.0) / sw,
                                jnp.float32(0.0))
                pm = jnp.sum(dp * wp, axis=1, keepdims=True) * inv
            else:
                pm = jnp.mean(dp, axis=1, keepdims=True)
            return jnp.broadcast_to(pm, (P, G // P, *d.shape[1:])
                                    ).reshape(d.shape)

        delta = jax.tree.map(pod_mean, delta)
        entry_w = weights
        if weights is not None:
            # per-entry pod weight sums (broadcast over each pod's
            # entries): the inner reduction weighs pod means by pod
            # liveness, and ring inners pick the [:, 0] representatives
            wp = jnp.asarray(weights, jnp.float32).reshape(P, -1)
            entry_w = jnp.broadcast_to(
                wp.sum(axis=1, keepdims=True), wp.shape).reshape(-1)
        return self.inner.sim_reduce(delta, residual, tc,
                                     num_pods=num_pods, pod_grouped=True,
                                     weights=entry_w)


@dataclass(frozen=True)
class Chunked(OuterSyncStrategy):
    """Span combinator: dispatch the Δθ leaf tree as ``num_chunks``
    contiguous spans, each its own XLA computation over ``inner``'s
    reduction, each carrying its own per-chunk dispatch state so apply can
    start on early-arriving chunks. Numerically identical to ``inner``
    (the per-leaf math never changes); only host dispatch order does."""

    inner: OuterSyncStrategy = FlatFP32()
    num_chunks: int = 2

    def __post_init__(self):
        if getattr(self.inner, "needs_residual2", False):
            raise ValueError(
                "Chunked cannot (yet) compose the reduce-scatter wire "
                "path: per-chunk threading of the second residual is a "
                "recorded follow-up (DESIGN.md §14); use rs-ag with "
                "chunks=1")

    @property
    def name(self) -> str:
        return f"chunked({self.num_chunks})[{self.inner.name}]"

    @property
    def needs_residual(self) -> bool:  # type: ignore[override]
        return self.inner.needs_residual

    @property
    def two_stage(self) -> bool:  # type: ignore[override]
        return self.inner.two_stage

    @property
    def wire_format(self) -> str:  # type: ignore[override]
        return self.inner.wire_format

    @property
    def sharded_state(self) -> bool:  # type: ignore[override]
        return self.inner.sharded_state

    def wire_bytes_per_param(self, tc) -> float:
        return self.inner.wire_bytes_per_param(tc)

    def transport_name(self, mesh=None) -> str:
        return self.inner.transport_name(mesh)

    def plan(self, pshapes, tc, mesh=None) -> SyncPlan:
        sizes = _leaf_sizes(pshapes)
        # clamp to the leaf count: more chunks than leaves would plan
        # empty spans (an empty tree keeps the fused single span, which
        # dispatch handles as a no-op computation)
        chunks = max(1, min(self.num_chunks, len(sizes)))
        spans = (balanced_spans(sizes, chunks) if sizes
                 else ((0, 0),))
        return SyncPlan(num_leaves=len(sizes), spans=spans,
                        needs_residual=self.needs_residual, name=self.name,
                        wire_format=self.wire_format,
                        transport=self.transport_name(mesh))

    def reduce_leaf(self, d, r, tc, ctx: ReduceCtx):
        return self.inner.reduce_leaf(d, r, tc, ctx)

    def sim_dispatch(self, group_params, outer, tc, *, mu, lr, num_pods=1,
                     weights=None):
        return self.inner.sim_dispatch(group_params, outer, tc, mu=mu,
                                       lr=lr, num_pods=num_pods,
                                       weights=weights)

    def sim_reduce(self, delta, residual, tc, *, num_pods=1,
                   pod_grouped=False, weights=None):
        return self.inner.sim_reduce(delta, residual, tc,
                                     num_pods=num_pods,
                                     pod_grouped=pod_grouped,
                                     weights=weights)


# ---------------------------------------------------------------------------
# resolution
# ---------------------------------------------------------------------------


def validate_pod_grouping(num_groups: int, num_pods: int) -> None:
    """The hierarchical two-stage reduce partitions the G groups into
    ``num_pods`` equal pods; an indivisible count used to surface as an
    opaque reshape error deep inside ``sim_reduce`` — fail loudly and
    early instead (plan time / run construction)."""
    P = max(int(num_pods), 1)
    if num_groups % P != 0:
        raise ValueError(
            f"hierarchical reduce needs num_pods ({P}) to divide the "
            f"group count ({num_groups}); got {num_groups} % {P} = "
            f"{num_groups % P}")


def resolve_strategy(cfg) -> OuterSyncStrategy:
    """Map an ``OuterCommConfig`` (or a ``TrainConfig`` carrying one) onto
    the equivalent strategy object. Every legacy flat-flag combination
    resolves here — the strategies are bit-identical to the flag branches
    they replaced (asserted by tests/test_sync_strategies.py)."""
    comm = getattr(cfg, "outer_comm", cfg)
    core: OuterSyncStrategy
    if comm.compression == "quantize":
        core = Quantized(bits=comm.bits, block=comm.block)
    elif comm.compression == "int8-wire":
        core = Int8Wire(bits=comm.bits, block=comm.block)
    elif comm.compression == "rs-ag":
        core = Int8Wire(bits=comm.bits, block=comm.block,
                        reduce_scatter=True)
    elif comm.compression == "none":
        core = FlatFP32()
    else:
        raise ValueError(f"unknown outer compression {comm.compression!r}")
    if getattr(comm, "sharded", False):
        core = Sharded(inner=core)
    if comm.hierarchical:
        core = Hierarchical(inner=core)
    if comm.chunks > 1:
        core = Chunked(inner=core, num_chunks=comm.chunks)
    return core


def strategy_name(*, bits: int = 32, block: int = 256,
                  hierarchical: bool = False, chunks: int = 1,
                  sharded: bool = False,
                  compression: Optional[str] = None) -> str:
    """Resolved-strategy name for benchmark knobs (bits >= 32 = fp32).

    ``compression`` pins the wire format explicitly (``"int8-wire"``,
    ``"rs-ag"``, ...); when ``None`` it is inferred from ``bits`` the
    legacy way (fp32 vs blockwise quantize)."""
    from repro.config import OuterCommConfig

    if compression is None:
        compression = "none" if bits >= 32 else "quantize"
    comm = OuterCommConfig(
        compression=compression,
        bits=bits if bits < 32 else 8, block=block,
        hierarchical=hierarchical, chunks=chunks, sharded=sharded)
    return resolve_strategy(comm).name
