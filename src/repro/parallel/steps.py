"""Distributed step functions: the Pier runtime on a real mesh.

Layout invariants (see DESIGN.md §3):

- **Manual axes** ``(pod, data_outer)``: Pier's relaxed axes. Params and
  AdamW state carry a leading group axis ``G = num_pods * data_outer``
  sharded over them — each group owns a (possibly divergent) model replica,
  stored sharded over its own ``data_inner × model`` slice.
- **Auto axes** ``(data_inner, model)``: GSPMD inserts all in-group
  communication (FSDP param all-gathers, gradient reduce-scatters over the
  in-group batch, TP collectives, MoE all-to-all) from sharding constraints.

Step functions:

- ``inner_step``   — Alg. 2 lines 5-8: group-local AdamW. Provably free of
  (pod, data_outer) collectives (asserted by tests on the lowered HLO).
- ``warmup_step``  — lazy-start/AdamW baseline: + global grad pmean.
- ``accumulate_step`` — Alg. 1 lines 4-7: outer-momentum accumulation (the
  eager fused path; donates the old outer state).
- ``accumulate_dispatch_step`` — the same accumulation as the dispatch half
  of a delayed warmup event (DESIGN.md §9): non-donating, so the
  pre-dispatch outer state stays live while the pending result is in
  flight; the apply half is a host-side install
  (``core.outer.warmup_apply`` — the warmup stale-delta correction is
  identically zero).
- ``outer_step``   — Alg. 2 lines 10-21: global Δθ pmean + Nesterov (eager,
  sync_delay=0 path).
- ``dispatch_step`` / ``apply_step`` — the same update split for delayed
  sync (sync_delay>0): dispatch launches the global Δθ pmean + Nesterov math
  without blocking the host, apply installs the target ``d`` steps later with
  the stale-delta correction (see core/outer.py and DESIGN.md).
- ``chunk_dispatch_steps`` / ``chunk_apply_steps`` — chunked dispatch and
  per-chunk apply (strategy plans with > 1 span, DESIGN.md §7): the Δθ
  tree is split into contiguous leaf spans, each reduced by its own XLA
  computation carrying its own per-chunk :class:`ChunkDispatch`, so early
  chunks' collectives run while later chunks are still being quantized —
  and early chunks *apply* (with their partial stale-delta correction)
  while later chunks' collectives are still in flight.
- ``serve_step`` / ``prefill_step`` — inference (plain GSPMD, no groups).

The outer collective itself is a pluggable :class:`OuterSyncStrategy`
(DESIGN.md §7, ``repro/sync/``): the strategy owns the per-leaf reduce
(flat fp32 pmean — the seed path, bit for bit — or hierarchical two-stage
and/or blockwise-quantized with an error-feedback residual carried
group-locally in ``OuterState.residual``) and the chunking plan; this
module only builds the jitted shard_map scaffolding around it. Every
jitted step in a :class:`StepBundle` is keyed off ONE strategy's plan —
a mid-run strategy switch (DESIGN.md §9) builds a fresh bundle (the
re-jit boundary; the Trainer caches bundles per strategy so switching
back is compile-free) and retargets ``OuterState.residual`` through
``init_residual`` when the residual requirement changes.

The steps' ops carry ``jax.named_scope`` scopes in their name stacks,
which a profiler trace shows: ``pier.blocks`` and ``pier.head`` (the model,
``jvp(...)`` forward and ``transpose(jvp(...))`` backward),
``pier.optimizer`` (clip, AdamW, the metrics' means), and in the outer
events ``pier.outer.delta`` (Δθ), ``pier.outer.exchange`` (the strategy's
per-leaf reduce) and ``pier.outer.update`` (the rest of each body).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat
from repro.config import ModelConfig, ParallelConfig, TrainConfig
from repro.core.outer import (OuterState, outer_apply, outer_init,
                              outer_reduce, outer_reduce_leaves,
                              outer_update, warmup_reduce)
from repro.launch import mesh as M
from repro.models import registry as R
from repro.optim.adamw import AdamWState, adamw_init, adamw_update
from repro.optim.clip import clip_by_global_norm
from repro.optim.schedules import lr_at
from repro.parallel import sharding as S
from repro.parallel.axes import pier_rules, use_rules
from repro.sync import (ChunkDispatch, OuterSyncStrategy, ReduceCtx,
                        SyncPlan, resolve_strategy)


class TrainState(NamedTuple):
    params: Any  # (G,)-stacked param tree
    opt: AdamWState  # (G,)-stacked


class DispatchState(NamedTuple):
    """An in-flight outer sync (sync_delay > 0): what apply needs later.

    ``target`` is the synchronized fp32 model produced from the global Δθ
    all-reduce; ``snapshot`` is each group's θ_dispatch, materialized as a
    fresh buffer because inner steps donate (and overwrite) the live params
    during the in-flight window.
    """

    target: Any  # fp32 param tree, identical across groups
    snapshot: Any  # (G,)-stacked param tree at dispatch time


@dataclass
class StepBundle:
    mesh: Mesh
    manual: Tuple[str, ...]
    num_groups: int
    strategy: OuterSyncStrategy
    plan: SyncPlan
    pspec: Any  # unstacked param specs
    stacked_pspec: Any
    state_shardings: Any
    outer_shardings: Any
    batch_sharding: Callable[[Any], Any]
    init_state: Callable
    init_outer: Callable
    inner_step: Callable
    warmup_step: Callable
    accumulate_step: Callable
    accumulate_dispatch_step: Callable
    outer_step: Callable
    dispatch_step: Callable
    apply_step: Callable
    eval_step: Callable
    # chunked dispatch / per-chunk apply (plan.num_chunks > 1): one jitted
    # dispatch computation per contiguous Δθ-leaf span, each returning its
    # own ChunkDispatch plus the span's updated outer leaves, and one
    # jitted apply per span installing that chunk's target. None when the
    # plan is a single fused span.
    chunk_dispatch_steps: Optional[Tuple[Callable, ...]] = None
    chunk_apply_steps: Optional[Tuple[Callable, ...]] = None
    # host-side: fold the per-chunk outer leaves back into one OuterState
    # (num_syncs advances exactly once per sync, regardless of chunks).
    stitch_outer: Optional[Callable] = None
    # residual retarget for mid-run strategy switches: materialize the
    # zero error-feedback residual (with this bundle's shardings) when the
    # incoming OuterState has none. None when the plan needs no residual.
    init_residual: Optional[Callable] = None
    # Elastic-membership variants (DESIGN.md §11), built only when
    # ``tc.membership`` is set — the fixed-membership graphs above stay
    # byte-for-byte unchanged otherwise. Weights/live masks are TRACED
    # (G,) arguments, so a mask change never re-jits.
    #   elastic_outer_step(state, outer, mu, olr, weights, live)
    #   elastic_dispatch_step(state, outer, mu, olr, weights)
    #   elastic_apply_step(state, dispatch, live)
    #   bootstrap_group(state, outer, g, donor_params) — reset group g's
    #     params to ``donor_params`` (anchor or checkpoint slice), fresh
    #     inner-opt state, zero residual; the rejoin bootstrap.
    elastic_outer_step: Optional[Callable] = None
    elastic_dispatch_step: Optional[Callable] = None
    elastic_apply_step: Optional[Callable] = None
    bootstrap_group: Optional[Callable] = None


def _param_shapes(mc: ModelConfig, scan_layers: bool = False):
    return jax.eval_shape(
        lambda k: R.init_params(k, mc, scan_layers=scan_layers),
        jax.ShapeDtypeStruct((2,), jnp.uint32))


def _stack(tree, g: int):
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (g, *x.shape)), tree)


def build_train_steps(
    mc: ModelConfig, tc: TrainConfig, pc: ParallelConfig, mesh: Mesh,
    strategy: Optional[OuterSyncStrategy] = None,
) -> StepBundle:
    strategy = strategy if strategy is not None else resolve_strategy(tc)
    manual = M.manual_axes(mesh)
    sizes = M.axis_sizes(mesh)
    G = 1
    for a in manual:
        G *= sizes[a]

    rules = pier_rules(
        have_pod="pod" in sizes, fsdp=pc.fsdp,
        shard_experts=pc.shard_experts, inside_manual=True,
        axis_sizes=sizes)

    # ---- sharding specs -------------------------------------------------
    pshapes = _param_shapes(mc, pc.scan_layers)
    pspec = S.param_specs(pshapes, mesh, pc)
    stacked_pspec = S.stack_spec(pspec, manual)
    opt_shapes = jax.eval_shape(lambda: adamw_init(pshapes, tc))
    opt_spec = AdamWState(
        count=P(manual),
        mu=S.param_specs(opt_shapes.mu, mesh, pc),
        nu=S.param_specs(opt_shapes.nu, mesh, pc))
    stacked_opt_spec = AdamWState(
        count=P(manual),
        mu=S.stack_spec(opt_spec.mu, manual),
        nu=S.stack_spec(opt_spec.nu, manual))
    state_spec = TrainState(params=stacked_pspec, opt=stacked_opt_spec)
    state_shardings = S.shardings(state_spec, mesh)
    plan = strategy.plan(pshapes, tc, mesh)
    compress = plan.needs_residual
    # The rs-ag wire path (DESIGN.md §14) carries a second error-feedback
    # residual over the re-quantized reduced shard; like the first it is
    # group-local (nonzero only on each group's own 1/E slot), so both
    # are (G,)-stacked.
    compress2 = getattr(plan, "needs_residual2", False)
    # The error-feedback residual is group-local (each group quantizes its
    # own payload), so unlike momentum/anchor it is (G,)-stacked.
    outer_spec = OuterState(
        momentum=S.param_specs(pshapes, mesh, pc),
        anchor=S.param_specs(pshapes, mesh, pc),
        num_syncs=P(),
        residual=(S.stack_spec(S.param_specs(pshapes, mesh, pc), manual)
                  if compress else None),
        residual2=(S.stack_spec(S.param_specs(pshapes, mesh, pc), manual)
                   if compress2 else None))
    outer_shardings = S.shardings(outer_spec, mesh)
    bspec = S.batch_spec(mesh)

    def batch_sharding(batch_shapes):
        return jax.tree.map(
            lambda x: NamedSharding(mesh, P(bspec[0], *([None] * (x.ndim - 1)))),
            batch_shapes)

    # ---- init ------------------------------------------------------------
    def init_state(rng) -> TrainState:
        def f(rng):
            params = R.init_params(rng, mc, scan_layers=pc.scan_layers)
            opt = adamw_init(params, tc)
            return TrainState(params=_stack(params, G), opt=AdamWState(
                count=jnp.zeros((G,), jnp.int32),
                mu=_stack(opt.mu, G), nu=_stack(opt.nu, G)))
        return jax.jit(f, out_shardings=state_shardings)(rng)

    def init_outer(state: TrainState) -> OuterState:
        def f(state):
            params = jax.tree.map(lambda x: x[0], state.params)
            return outer_init(params, tc, num_groups=G,
                              needs_residual=compress,
                              needs_residual2=compress2)
        return jax.jit(f, out_shardings=outer_shardings)(state)

    # ---- the shared inner/warmup body -------------------------------------
    def grads_and_loss(params, batch, step):
        nm = pc.num_microbatches

        def lfn(p, b):
            return R.loss_fn(p, mc, b, use_pallas=pc.use_pallas,
                             remat=pc.remat)

        if nm == 1:
            (loss, metrics), grads = jax.value_and_grad(
                lfn, has_aux=True)(params, batch)
            return grads, loss
        micro = jax.tree.map(
            lambda x: x.reshape(nm, x.shape[0] // nm, *x.shape[1:]), batch)

        def mb_body(acc, b):
            g_acc, l_acc = acc
            (loss, _), grads = jax.value_and_grad(lfn, has_aux=True)(params, b)
            return (jax.tree.map(jnp.add, g_acc, grads), l_acc + loss), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        acc0 = (zeros, jnp.float32(0))
        if manual:
            # grads are varying over the manual (group) axes; the zero init
            # must carry the same varying-mesh-axes annotation for the scan
            acc0 = compat.mark_varying(acc0, tuple(manual))
        (gsum, lsum), _ = jax.lax.scan(mb_body, acc0, micro)
        inv = 1.0 / nm
        return jax.tree.map(lambda g: g * inv, gsum), lsum * inv

    def make_sgd_body(global_sync: bool):
        def body(state: TrainState, batch, step):
            with use_rules(rules):
                params = jax.tree.map(lambda x: x[0], state.params)
                opt = jax.tree.map(lambda x: x[0], state.opt)
                grads, loss = grads_and_loss(params, batch, step)
                if global_sync and manual:
                    grads = jax.lax.pmean(grads, manual)
                with jax.named_scope("pier.optimizer"):
                    grads, gnorm = clip_by_global_norm(grads, tc.clip_grad)
                    lr = lr_at(tc, step)
                    new_params, new_opt = adamw_update(grads, opt, params,
                                                       tc, lr)
                    metrics = {
                        "loss": (jax.lax.pmean(loss, manual) if manual
                                 else loss),
                        "grad_norm": (jax.lax.pmean(gnorm, manual)
                                      if manual else gnorm),
                        "lr": lr,
                    }
                    # in the scope: XLA roots the fused AdamW update at this
                    # (G,)-stacking, and the fusion takes the root's scope
                    new_state = TrainState(
                        params=jax.tree.map(lambda x: x[None], new_params),
                        opt=jax.tree.map(lambda x: x[None], new_opt))
                return new_state, metrics
        return body

    def wrap_state_step(body):
        in_specs = (
            TrainState(
                params=jax.tree.map(lambda _: P(manual), state_spec.params,
                                    is_leaf=lambda s: isinstance(s, P)),
                opt=jax.tree.map(lambda _: P(manual), state_spec.opt,
                                 is_leaf=lambda s: isinstance(s, P))),
            P(manual),  # batch dim 0 (manual part; data_inner rides auto)
            P(),  # step
        )
        out_specs = (in_specs[0], P())

        def stepfn(state, batch, step):
            batch_specs = jax.tree.map(
                lambda x: P(manual, *([None] * (x.ndim - 1))), batch)
            f = compat.shard_map(
                body, mesh=mesh,
                in_specs=(in_specs[0], batch_specs, P()),
                out_specs=out_specs,
                axis_names=set(manual))
            return f(state, batch, step)

        return jax.jit(stepfn, donate_argnums=(0,))

    inner_step = wrap_state_step(make_sgd_body(global_sync=False))
    warmup_step = wrap_state_step(make_sgd_body(global_sync=True))

    # ---- outer events -----------------------------------------------------
    # Shared shard_map specs. The outer state is replicated across groups
    # except the error-feedback residual, which is group-local (stacked).
    _sspec = lambda: TrainState(
        params=jax.tree.map(lambda _: P(manual), state_spec.params,
                            is_leaf=lambda s: isinstance(s, P)),
        opt=jax.tree.map(lambda _: P(manual), state_spec.opt,
                         is_leaf=lambda s: isinstance(s, P)))

    def _ospec():
        rep = lambda t: jax.tree.map(lambda _: P(), t,
                                     is_leaf=lambda s: isinstance(s, P))
        return OuterState(
            momentum=rep(outer_spec.momentum),
            anchor=rep(outer_spec.anchor),
            num_syncs=P(),
            residual=(jax.tree.map(lambda _: P(manual), outer_spec.residual,
                                   is_leaf=lambda s: isinstance(s, P))
                      if compress else None),
            residual2=(jax.tree.map(lambda _: P(manual),
                                    outer_spec.residual2,
                                    is_leaf=lambda s: isinstance(s, P))
                       if compress2 else None))

    _dspec = lambda sspec: DispatchState(
        target=jax.tree.map(lambda _: P(), sspec.params,
                            is_leaf=lambda s: isinstance(s, P)),
        snapshot=sspec.params)

    fast_axes = tuple(a for a in manual if a != "pod")
    slow_axes = tuple(a for a in manual if a == "pod")
    auto_axes = tuple(a for a in mesh.axis_names if a not in manual)
    # The mesh-axis context threaded to the strategy's per-leaf reduce:
    # the exchange starts at the full manual set; the hierarchical
    # combinator narrows it to the slow axes after its fast-domain mean.
    # axis_sizes gives wire strategies (plan.wire_format != "fp32") their
    # static ring-endpoint counts — their hop loops unroll at trace time —
    # and sharded strategies their auto-axis shard count (block alignment).
    # The mesh rides along because constraints inside partial-manual
    # shard_map must be NamedShardings on jax 0.4.x (sync/base.py).
    reduce_ctx = ReduceCtx(manual=manual, fast_axes=fast_axes,
                           slow_axes=slow_axes, exchange_axes=manual,
                           use_pallas=pc.use_pallas,
                           axis_sizes={a: int(sizes[a])
                                       for a in mesh.axis_names},
                           auto_axes=auto_axes, mesh=mesh)
    # Per-leaf PartitionSpecs over the auto axes, in Δθ leaf order —
    # threaded to sharded strategies through ``ReduceCtx.leaf_spec``.
    pspec_flat = jax.tree_util.tree_leaves(
        pspec, is_leaf=lambda s: isinstance(s, P))
    sharded_state = bool(getattr(strategy, "sharded_state", False))

    # Wire strategies also need each shard's coordinate along the manual
    # axes (the canonical ring-slot index). jax 0.4.x cannot lower
    # lax.axis_index inside partial-manual shard_map, so the coordinates
    # enter as data: an arange sharded over each axis, sliced per shard.
    def _coord_inputs():
        return {a: jnp.arange(sizes[a], dtype=jnp.int32) for a in manual}

    def _coord_spec():
        return {a: P(a) for a in manual}

    def _local_ctx(coords):
        return reduce_ctx.with_coords({a: c[0] for a, c in coords.items()})

    def _global_pmean(tree):
        """Flat or two-stage pmean over the manual axes (same mean)."""
        if not manual:
            return tree
        if strategy.two_stage:
            if fast_axes:
                tree = jax.lax.pmean(tree, fast_axes)
            if slow_axes:
                tree = jax.lax.pmean(tree, slow_axes)
            return tree
        return jax.lax.pmean(tree, manual)

    @jax.named_scope("pier.outer.exchange")
    def _reduce_delta_leaf(d, r, ctx=reduce_ctx, spec=None):
        """One Δθ leaf -> (globally averaged payload, new residual | None).

        Delegates to the strategy: flat fp32 pmean is the seed collective
        bit for bit; hierarchical / quantized strategies stage and
        compress the payload (DESIGN.md §6/§7); the int8-wire strategy
        ring-exchanges the packed payload itself (DESIGN.md §8), using
        the shard coordinates carried on ``ctx``; sharded strategies pin
        the leaf to ``spec`` (its auto-axis PartitionSpec) so only the
        per-device shard is compressed and exchanged (DESIGN.md §10).
        """
        return strategy.reduce_leaf(d, r, tc, ctx.with_leaf_spec(spec))

    @jax.named_scope("pier.outer.delta")
    def _reduced_delta(params, outer, ctx=reduce_ctx):
        """(delta_avg tree, new residual tree | None) for one group.

        Under the rs-ag wire path (``compress2``) each leaf's residual
        travels as an opaque ``(r1, r2)`` pair and ``new_res`` comes back
        as the ``(tree_r1, tree_r2)`` pair ``_residual_kw`` unpacks."""
        delta = jax.tree.map(
            lambda p, a: p.astype(jnp.float32) - a.astype(jnp.float32),
            params, outer.anchor)
        res = (jax.tree.map(lambda x: x[0], outer.residual)
               if compress else None)
        flat_d, treedef = jax.tree_util.tree_flatten(delta)
        flat_r = (treedef.flatten_up_to(res) if compress
                  else [None] * len(flat_d))
        if compress2:
            res2 = jax.tree.map(lambda x: x[0], outer.residual2)
            flat_r2 = treedef.flatten_up_to(res2)
            flat_r = [(r1, r2) for r1, r2 in zip(flat_r, flat_r2)]
        out = [_reduce_delta_leaf(d, r, ctx, spec)
               for d, r, spec in zip(flat_d, flat_r, pspec_flat)]
        unf = jax.tree_util.tree_unflatten
        delta_avg = unf(treedef, [p for p, _ in out])
        if compress2:
            new_res = (
                unf(treedef, [jnp.expand_dims(r[0], 0) for _, r in out]),
                unf(treedef, [jnp.expand_dims(r[1], 0) for _, r in out]))
        else:
            new_res = (unf(treedef, [jnp.expand_dims(r, 0) for _, r in out])
                       if compress else None)
        return delta_avg, new_res

    def _residual_kw(new_res):
        if compress2:
            return {"residual": new_res[0], "residual2": new_res[1]}
        return {"residual": new_res} if compress else {}

    def accumulate_body(state, outer, mu):
        with use_rules(rules), jax.named_scope("pier.outer.update"):
            params = jax.tree.map(lambda x: x[0], state.params)
            if manual:
                # During warmup all groups hold identical params (they run
                # globally synced AdamW), but the VMA checker cannot prove
                # it — pmean is the identity here and makes it explicit.
                params = _global_pmean(params)
            return warmup_reduce(outer, params, mu)

    def accumulate_fn(state, outer, mu):
        f = compat.shard_map(
            accumulate_body, mesh=mesh,
            in_specs=(_sspec(), _ospec(), P()),
            out_specs=_ospec(),
            axis_names=set(manual))
        return f(state, outer, mu)

    # Sharded strategies pin every outer-event output to the param_specs
    # layouts via jit out_shardings (in-body constraints guide GSPMD, the
    # out_shardings make the ~1/(TP×FSDP) outer-state scaling a guarantee
    # rather than a propagation outcome). Replicated strategies keep the
    # seed behavior: layouts left to GSPMD.
    _out_sh = (lambda sh: {"out_shardings": sh}) if sharded_state \
        else (lambda sh: {})
    dispatch_shardings = DispatchState(
        target=S.shardings(pspec, mesh),
        snapshot=S.shardings(stacked_pspec, mesh))

    accumulate_step = jax.jit(accumulate_fn, donate_argnums=(1,),
                              **_out_sh(outer_shardings))
    # the dispatch half of a delayed warmup event: identical math, but the
    # old outer state is NOT donated — it stays the live state while the
    # pending result is in flight (the apply half installs it host-side;
    # core.outer.warmup_apply documents why the correction is zero).
    accumulate_dispatch_step = jax.jit(accumulate_fn,
                                       **_out_sh(outer_shardings))

    def outer_body(state, outer, mu, olr, coords):
        with use_rules(rules), jax.named_scope("pier.outer.update"):
            params = jax.tree.map(lambda x: x[0], state.params)
            delta, new_res = _reduced_delta(
                params, outer, _local_ctx(coords))  # THE collective
            new_params_f32, new_outer = outer_update(
                outer, delta, tc, mu=mu, lr=olr, use_pallas=pc.use_pallas,
                **_residual_kw(new_res))
            new_params = jax.tree.map(
                lambda f32, p: f32.astype(p.dtype)[None],
                new_params_f32, params)
            new_state = TrainState(params=new_params, opt=state.opt)
            return new_state, new_outer

    def outer_fn(state, outer, mu, olr):
        sspec, ospec = _sspec(), _ospec()
        f = compat.shard_map(
            outer_body, mesh=mesh,
            in_specs=(sspec, ospec, P(), P(), _coord_spec()),
            out_specs=(sspec, ospec),
            axis_names=set(manual))
        return f(state, outer, mu, olr, _coord_inputs())

    outer_step = jax.jit(outer_fn, donate_argnums=(0, 1),
                         **_out_sh((state_shardings, outer_shardings)))

    # ---- delayed outer sync (dispatch / apply) -----------------------------
    # dispatch launches THE global collective and the Nesterov math; the host
    # does not block on it (jax dispatch is async), so the all-reduce runs
    # concurrently with the next ``sync_delay`` inner steps. apply installs
    # the target with the stale-delta correction once the window closes.
    def dispatch_body(state, outer, mu, olr, coords):
        with use_rules(rules), jax.named_scope("pier.outer.update"):
            params = jax.tree.map(lambda x: x[0], state.params)
            delta, new_res = _reduced_delta(
                params, outer, _local_ctx(coords))  # THE collective
            target_f32, new_outer = outer_reduce(
                outer, delta, tc, mu=mu, lr=olr, use_pallas=pc.use_pallas,
                **_residual_kw(new_res))
            dispatch = DispatchState(
                target=target_f32,
                snapshot=jax.tree.map(lambda x: x[None], params))
            return dispatch, new_outer

    def dispatch_fn(state, outer, mu, olr):
        sspec, ospec = _sspec(), _ospec()
        dspec = _dspec(sspec)
        f = compat.shard_map(
            dispatch_body, mesh=mesh,
            in_specs=(sspec, ospec, P(), P(), _coord_spec()),
            out_specs=(dspec, ospec),
            axis_names=set(manual))
        return f(state, outer, mu, olr, _coord_inputs())

    # NOTE: the train state is NOT donated — the snapshot output forces a
    # fresh copy of the params while inner steps keep donating the live ones.
    dispatch_step = jax.jit(dispatch_fn, donate_argnums=(1,),
                            **_out_sh((dispatch_shardings,
                                       outer_shardings)))

    # ---- chunked dispatch + per-chunk apply (plan.num_chunks > 1) ----------
    # The Δθ leaves are split into contiguous spans; each span's reduce AND
    # its slice of the Nesterov update is its own jitted computation, so the
    # host enqueues them back to back and chunk k's collective overlaps
    # chunk k+1's quantization/compute. Each chunk returns its own
    # ChunkDispatch (targets + snapshots for the span), so the later
    # per-chunk applies install early-arriving chunks while late chunks'
    # collectives are still in flight (partial stale-delta correction per
    # span). Per-leaf math is identical to the fused dispatch
    # (outer_reduce_leaves is shared), so chunking never changes numerics.
    chunk_dispatch_steps = None
    chunk_apply_steps = None
    stitch_outer = None
    if plan.num_chunks > 1:
        pflat_shapes, ptreedef = jax.tree_util.tree_flatten(pshapes)
        spans = plan.spans
        stacked_pspec_flat = jax.tree_util.tree_leaves(
            stacked_pspec, is_leaf=lambda s: isinstance(s, P))

        def _span_shardings(lo, hi):
            """Per-span out_shardings (sharded strategies): targets /
            momentum / anchor at the unstacked per-leaf specs, snapshots /
            residual at the (G,)-stacked ones."""
            ns = lambda spec: NamedSharding(mesh, spec)
            unstacked = tuple(ns(pspec_flat[j]) for j in range(lo, hi))
            stacked = tuple(ns(stacked_pspec_flat[j]) for j in range(lo, hi))
            return (ChunkDispatch(targets=unstacked, snapshots=stacked),
                    (unstacked, unstacked, stacked if compress else ()))

        def make_chunk_dispatch(lo, hi):
            def chunk_body(state, outer, mu, olr, coords):
                with use_rules(rules), jax.named_scope("pier.outer.update"):
                    ctx = _local_ctx(coords)
                    params = jax.tree.map(lambda x: x[0], state.params)
                    p_flat = ptreedef.flatten_up_to(params)
                    a_flat = ptreedef.flatten_up_to(outer.anchor)
                    m_flat = ptreedef.flatten_up_to(outer.momentum)
                    r_flat = (ptreedef.flatten_up_to(jax.tree.map(
                        lambda x: x[0], outer.residual))
                        if compress else [None] * len(p_flat))
                    payload, new_res, snaps = [], [], []
                    for j in range(lo, hi):
                        with jax.named_scope("pier.outer.delta"):
                            d = (p_flat[j].astype(jnp.float32)
                                 - a_flat[j].astype(jnp.float32))
                        da, nr = _reduce_delta_leaf(d, r_flat[j], ctx,
                                                    pspec_flat[j])
                        payload.append(da)
                        if compress:
                            new_res.append(jnp.expand_dims(nr, 0))
                        snaps.append(jnp.expand_dims(p_flat[j], 0))
                    targets, new_m, new_anchor = outer_reduce_leaves(
                        m_flat[lo:hi], a_flat[lo:hi], payload, tc,
                        mu=mu, lr=olr, use_pallas=pc.use_pallas)
                    chunk = ChunkDispatch(targets=tuple(targets),
                                          snapshots=tuple(snaps))
                    return chunk, (tuple(new_m), tuple(new_anchor),
                                   tuple(new_res))

            def chunk_fn(state, outer, mu, olr):
                n = hi - lo
                chunk_spec = ChunkDispatch(
                    targets=tuple(P() for _ in range(n)),
                    snapshots=tuple(P(manual) for _ in range(n)))
                leaves_spec = (tuple(P() for _ in range(n)),
                               tuple(P() for _ in range(n)),
                               (tuple(P(manual) for _ in range(n))
                                if compress else ()))
                f = compat.shard_map(
                    chunk_body, mesh=mesh,
                    in_specs=(_sspec(), _ospec(), P(), P(), _coord_spec()),
                    out_specs=(chunk_spec, leaves_spec),
                    axis_names=set(manual))
                return f(state, outer, mu, olr, _coord_inputs())

            # NOTE: neither state (snapshots force fresh buffers) nor outer
            # (read by every chunk computation) is donated here; the outer
            # copy is retired host-side by stitch_outer after the last chunk.
            return jax.jit(chunk_fn, **_out_sh(_span_shardings(lo, hi)))

        chunk_dispatch_steps = tuple(
            make_chunk_dispatch(lo, hi) for lo, hi in spans)

        def make_chunk_apply(lo, hi):
            def apply_chunk_body(state, chunk):
                with use_rules(rules), jax.named_scope("pier.outer.update"):
                    params = jax.tree.map(lambda x: x[0], state.params)
                    p_flat = ptreedef.flatten_up_to(params)
                    span = tuple(p_flat[lo:hi])
                    snaps = tuple(s[0] for s in chunk.snapshots)
                    new_span = outer_apply(chunk.targets, snaps, span)
                    p_flat[lo:hi] = list(new_span)
                    new_params = jax.tree_util.tree_unflatten(
                        ptreedef, p_flat)
                    return TrainState(
                        params=jax.tree.map(lambda x: x[None], new_params),
                        opt=state.opt)

            def apply_chunk_fn(state, chunk):
                n = hi - lo
                sspec = _sspec()
                chunk_spec = ChunkDispatch(
                    targets=tuple(P() for _ in range(n)),
                    snapshots=tuple(P(manual) for _ in range(n)))
                f = compat.shard_map(
                    apply_chunk_body, mesh=mesh,
                    in_specs=(sspec, chunk_spec),
                    out_specs=sspec,
                    axis_names=set(manual))
                return f(state, chunk)

            return jax.jit(apply_chunk_fn, donate_argnums=(0, 1),
                           **_out_sh(state_shardings))

        chunk_apply_steps = tuple(
            make_chunk_apply(lo, hi) for lo, hi in spans)

        def stitch_outer(outer, chunk_leaves):
            """Fold per-chunk outer leaves into one OuterState (host-side).

            ``chunk_leaves`` holds each chunk's (momentum, anchor, residual)
            span tuples in span order; num_syncs advances exactly once per
            sync regardless of the chunk count.
            """
            m_leaves, a_leaves, r_leaves = [], [], []
            for nm, na, nr in chunk_leaves:
                m_leaves.extend(nm)
                a_leaves.extend(na)
                r_leaves.extend(nr)
            unf = jax.tree_util.tree_unflatten
            return OuterState(
                momentum=unf(ptreedef, m_leaves),
                anchor=unf(ptreedef, a_leaves),
                num_syncs=outer.num_syncs + 1,
                residual=unf(ptreedef, r_leaves) if compress else None)

    # ---- residual retarget (mid-run strategy switches, DESIGN.md §9) ------
    init_residual = None
    if compress:
        _res_shardings = S.shardings(outer_spec.residual, mesh)

        def init_residual(state):
            """Zero error-feedback residual, (G,)-stacked like outer_init's.

            Used when a strategy switch moves from a residual-free plan to
            a compressed one: momentum/anchor carry over, the residual
            starts at zero — exactly the first-sync semantics of
            ``compress_delta(residual=None)``, now materialized so the
            stacked shardings match this bundle's specs. The Trainer also
            reuses it to materialize ``residual2`` when a switch lands on
            the rs-ag wire path (same zero tree, same stacked shardings).
            """
            def f(state):
                params = jax.tree.map(lambda x: x[0], state.params)
                return jax.tree.map(
                    lambda p: jnp.zeros((G, *p.shape), jnp.float32), params)
            return jax.jit(f, out_shardings=_res_shardings)(state)

    def apply_body(state, dispatch):
        with use_rules(rules), jax.named_scope("pier.outer.update"):
            params = jax.tree.map(lambda x: x[0], state.params)
            snap = jax.tree.map(lambda x: x[0], dispatch.snapshot)
            new_params = outer_apply(dispatch.target, snap, params)
            new_state = TrainState(
                params=jax.tree.map(lambda x: x[None], new_params),
                opt=state.opt)
            return new_state

    def apply_fn(state, dispatch):
        sspec = _sspec()
        dspec = _dspec(sspec)
        f = compat.shard_map(
            apply_body, mesh=mesh,
            in_specs=(sspec, dspec),
            out_specs=sspec,
            axis_names=set(manual))
        return f(state, dispatch)

    apply_step = jax.jit(apply_fn, donate_argnums=(0, 1),
                         **_out_sh(state_shardings))

    # ---- elastic membership (DESIGN.md §11) --------------------------------
    # Weighted variable-membership variants of the outer events, built ONLY
    # when tc.membership is set: the per-event (G,) participation weights
    # and apply-live mask enter as traced, replicated data (a mask change
    # never re-jits), each shard slices its own group's weight by its
    # linearized manual coordinate (the same data-threading pattern as
    # axis_coords), and the strategy reduces with ×1/Σw normalization —
    # bit-identical to the fixed path at all-ones weights.
    elastic_outer_step = None
    elastic_dispatch_step = None
    elastic_apply_step = None
    bootstrap_group = None
    if tc.membership is not None:
        if plan.num_chunks > 1:
            raise NotImplementedError(
                "elastic membership does not compose with chunked "
                "dispatch yet (per-chunk weighted applies are a recorded "
                "follow-up) — drop --comm-chunks or membership")

        def _linear_idx(coords):
            """Row-major linearized manual coordinate == the group index
            (and the canonical wire-source slot)."""
            idx = jnp.int32(0)
            for a in manual:
                idx = idx * jnp.int32(sizes[a]) + coords[a]
            return idx

        def _member_ctx(coords, weights):
            local = {a: c[0] for a, c in coords.items()}
            ctx = reduce_ctx.with_coords(local)
            if not manual:
                return ctx.with_membership(weights, weights[0])
            w = jax.lax.dynamic_index_in_dim(
                weights, _linear_idx(local), 0, keepdims=False)
            return ctx.with_membership(weights, w)

        def _live_here(live, coords):
            local = {a: c[0] for a, c in coords.items()}
            if not manual:
                return live[0]
            return jax.lax.dynamic_index_in_dim(
                live, _linear_idx(local), 0, keepdims=False)

        def elastic_outer_body(state, outer, mu, olr, coords, weights,
                               live):
            with use_rules(rules), jax.named_scope("pier.outer.update"):
                params = jax.tree.map(lambda x: x[0], state.params)
                delta, new_res = _reduced_delta(
                    params, outer, _member_ctx(coords, weights))
                new_params_f32, new_outer = outer_update(
                    outer, delta, tc, mu=mu, lr=olr,
                    use_pallas=pc.use_pallas, **_residual_kw(new_res))
                lg = _live_here(live, coords)
                new_params = jax.tree.map(
                    lambda f32, p: jnp.where(
                        lg, f32.astype(p.dtype), p)[None],
                    new_params_f32, params)
                new_state = TrainState(params=new_params, opt=state.opt)
                return new_state, new_outer

        def elastic_outer_fn(state, outer, mu, olr, weights, live):
            sspec, ospec = _sspec(), _ospec()
            f = compat.shard_map(
                elastic_outer_body, mesh=mesh,
                in_specs=(sspec, ospec, P(), P(), _coord_spec(), P(), P()),
                out_specs=(sspec, ospec),
                axis_names=set(manual))
            return f(state, outer, mu, olr, _coord_inputs(), weights, live)

        elastic_outer_step = jax.jit(
            elastic_outer_fn, donate_argnums=(0, 1),
            **_out_sh((state_shardings, outer_shardings)))

        def elastic_dispatch_body(state, outer, mu, olr, coords, weights):
            with use_rules(rules), jax.named_scope("pier.outer.update"):
                params = jax.tree.map(lambda x: x[0], state.params)
                delta, new_res = _reduced_delta(
                    params, outer, _member_ctx(coords, weights))
                target_f32, new_outer = outer_reduce(
                    outer, delta, tc, mu=mu, lr=olr,
                    use_pallas=pc.use_pallas, **_residual_kw(new_res))
                dispatch = DispatchState(
                    target=target_f32,
                    snapshot=jax.tree.map(lambda x: x[None], params))
                return dispatch, new_outer

        def elastic_dispatch_fn(state, outer, mu, olr, weights):
            sspec, ospec = _sspec(), _ospec()
            dspec = _dspec(sspec)
            f = compat.shard_map(
                elastic_dispatch_body, mesh=mesh,
                in_specs=(sspec, ospec, P(), P(), _coord_spec(), P()),
                out_specs=(dspec, ospec),
                axis_names=set(manual))
            return f(state, outer, mu, olr, _coord_inputs(), weights)

        elastic_dispatch_step = jax.jit(
            elastic_dispatch_fn, donate_argnums=(1,),
            **_out_sh((dispatch_shardings, outer_shardings)))

        def elastic_apply_body(state, dispatch, coords, live):
            with use_rules(rules), jax.named_scope("pier.outer.update"):
                params = jax.tree.map(lambda x: x[0], state.params)
                snap = jax.tree.map(lambda x: x[0], dispatch.snapshot)
                applied = outer_apply(dispatch.target, snap, params)
                lg = _live_here(live, coords)
                new_params = jax.tree.map(
                    lambda n, o: jnp.where(lg, n, o), applied, params)
                return TrainState(
                    params=jax.tree.map(lambda x: x[None], new_params),
                    opt=state.opt)

        def elastic_apply_fn(state, dispatch, live):
            sspec = _sspec()
            dspec = _dspec(sspec)
            f = compat.shard_map(
                elastic_apply_body, mesh=mesh,
                in_specs=(sspec, dspec, _coord_spec(), P()),
                out_specs=sspec,
                axis_names=set(manual))
            return f(state, dispatch, _coord_inputs(), live)

        elastic_apply_step = jax.jit(
            elastic_apply_fn, donate_argnums=(0, 1),
            **_out_sh(state_shardings))

        def bootstrap_body(state, outer, g, donor, coords):
            with use_rules(rules):
                local = {a: c[0] for a, c in coords.items()}
                is_g = (_linear_idx(local) == g) if manual \
                    else jnp.bool_(True)
                new_params = jax.tree.map(
                    lambda p, dn: jnp.where(
                        is_g, dn.astype(p.dtype)[None], p),
                    state.params, donor)
                new_opt = AdamWState(
                    count=jnp.where(is_g, jnp.zeros_like(state.opt.count),
                                    state.opt.count),
                    mu=jax.tree.map(
                        lambda m: jnp.where(is_g, jnp.zeros_like(m), m),
                        state.opt.mu),
                    nu=jax.tree.map(
                        lambda n: jnp.where(is_g, jnp.zeros_like(n), n),
                        state.opt.nu))
                new_res = (jax.tree.map(
                    lambda r: jnp.where(is_g, jnp.zeros_like(r), r),
                    outer.residual) if compress else None)
                new_res2 = (jax.tree.map(
                    lambda r: jnp.where(is_g, jnp.zeros_like(r), r),
                    outer.residual2) if compress2 else None)
                new_outer = OuterState(
                    momentum=outer.momentum, anchor=outer.anchor,
                    num_syncs=outer.num_syncs, residual=new_res,
                    residual2=new_res2)
                return TrainState(params=new_params, opt=new_opt), new_outer

        def bootstrap_fn(state, outer, g, donor):
            sspec, ospec = _sspec(), _ospec()
            donor_spec = jax.tree.map(lambda _: P(), pspec,
                                      is_leaf=lambda s: isinstance(s, P))
            f = compat.shard_map(
                bootstrap_body, mesh=mesh,
                in_specs=(sspec, ospec, P(), donor_spec, _coord_spec()),
                out_specs=(sspec, ospec),
                axis_names=set(manual))
            return f(state, outer, g, donor, _coord_inputs())

        # outer is NOT donated: the anchor-donor call passes outer.anchor
        # as ``donor`` too, and a donated buffer cannot also be a live
        # argument (f(donate(a), a)); bootstraps are rare, the copy is fine
        bootstrap_group = jax.jit(
            bootstrap_fn, donate_argnums=(0,),
            **_out_sh((state_shardings, outer_shardings)))

    # ---- eval --------------------------------------------------------------
    def eval_body(state, batch):
        with use_rules(rules):
            params = jax.tree.map(lambda x: x[0], state.params)
            loss, _ = R.loss_fn(params, mc, batch, use_pallas=pc.use_pallas)
            return jax.lax.pmean(loss, manual) if manual else loss

    def eval_fn(state, batch):
        sspec = TrainState(
            params=jax.tree.map(lambda _: P(manual), state_spec.params,
                                is_leaf=lambda s: isinstance(s, P)),
            opt=jax.tree.map(lambda _: P(manual), state_spec.opt,
                             is_leaf=lambda s: isinstance(s, P)))
        batch_specs = jax.tree.map(
            lambda x: P(manual, *([None] * (x.ndim - 1))), batch)
        f = compat.shard_map(eval_body, mesh=mesh,
                          in_specs=(sspec, batch_specs), out_specs=P(),
                          axis_names=set(manual))
        return f(state, batch)

    eval_step = jax.jit(eval_fn)

    return StepBundle(
        mesh=mesh, manual=manual, num_groups=G,
        strategy=strategy, plan=plan,
        pspec=pspec, stacked_pspec=stacked_pspec,
        state_shardings=state_shardings, outer_shardings=outer_shardings,
        batch_sharding=batch_sharding,
        init_state=init_state, init_outer=init_outer,
        inner_step=inner_step, warmup_step=warmup_step,
        accumulate_step=accumulate_step,
        accumulate_dispatch_step=accumulate_dispatch_step,
        outer_step=outer_step,
        dispatch_step=dispatch_step, apply_step=apply_step,
        eval_step=eval_step,
        chunk_dispatch_steps=chunk_dispatch_steps,
        chunk_apply_steps=chunk_apply_steps,
        stitch_outer=stitch_outer,
        init_residual=init_residual,
        elastic_outer_step=elastic_outer_step,
        elastic_dispatch_step=elastic_dispatch_step,
        elastic_apply_step=elastic_apply_step,
        bootstrap_group=bootstrap_group)


# ===========================================================================
# Serving (no group structure: plain GSPMD over the whole mesh)
# ===========================================================================


@dataclass
class ServeBundle:
    mesh: Mesh
    pspec: Any
    param_shardings: Any
    state_shardings: Any
    serve_step: Callable
    prefill_step: Callable
    init_state: Callable


def build_serve_steps(
    mc: ModelConfig, pc: ParallelConfig, mesh: Mesh, *,
    batch: int, max_len: int,
) -> ServeBundle:
    rules = pier_rules(
        have_pod="pod" in mesh.axis_names, fsdp=pc.fsdp,
        shard_experts=pc.shard_experts, inside_manual=False,
        context_parallel_seq=pc.context_parallel,
        axis_sizes=M.axis_sizes(mesh))

    pshapes = _param_shapes(mc, pc.scan_layers)
    pspec = S.param_specs(pshapes, mesh, pc)
    param_shardings = S.shardings(pspec, mesh)

    state_shapes = jax.eval_shape(
        lambda: R.init_decode_state(mc, batch, max_len,
                                    scan_layers=pc.scan_layers))
    sspec = S.decode_state_specs(
        state_shapes, mesh, pc, context_parallel=pc.context_parallel)
    state_shardings = S.shardings(sspec, mesh)

    # NOTE: MoE "indexed" dispatch was evaluated for serving (§Perf pair 3)
    # and REGRESSES memory 5.7x for a 16% collective win — serving stays on
    # the flat dispatch; see experiments/perf/SUMMARY.md.
    def serve(params, state, tokens):
        with use_rules(rules):
            return R.decode_step(params, mc, state, tokens)

    def prefill(params, batch_in):
        with use_rules(rules):
            logits, state = R.prefill(params, mc, batch_in, max_len=max_len,
                                      use_pallas=pc.use_pallas)
            # serving semantics: only the next-token logits leave the step
            return logits[:, -1:], state

    def init_state():
        return jax.jit(
            lambda: R.init_decode_state(mc, batch, max_len,
                                        scan_layers=pc.scan_layers),
            out_shardings=state_shardings)()

    # Serving is plain GSPMD (no shard_map); constraints need the mesh in
    # scope during trace -> wrap the jitted callables in jax.set_mesh.
    def _with_mesh(fn):
        def call(*args, **kw):
            with compat.mesh_context(mesh):
                return fn(*args, **kw)
        call.lower = lambda *a, **k: _lower_with_mesh(fn, mesh, *a, **k)
        return call

    def _lower_with_mesh(fn, mesh, *a, **k):
        with compat.mesh_context(mesh):
            return fn.lower(*a, **k)

    serve_step = _with_mesh(jax.jit(serve, donate_argnums=(1,)))
    prefill_step = _with_mesh(jax.jit(prefill))

    return ServeBundle(
        mesh=mesh, pspec=pspec, param_shardings=param_shardings,
        state_shardings=state_shardings, serve_step=serve_step,
        prefill_step=prefill_step, init_state=init_state)


# ===========================================================================
# Paged serving (continuous batching over a shared KV block pool, §12)
# ===========================================================================


@dataclass
class PagedServeBundle:
    """Jitted steps for the paged decode path (``repro.serve``).

    ``decode_step(params, pools, tokens, positions, block_tables,
    context_lens)`` donates the pools; ``prefill_step(params, tokens,
    pools, block_table, last_index)`` re-jits per padded prompt length —
    prompts are padded to a block multiple, so the bucket count is
    ``max_prompt / block_size``, not ``max_prompt``.
    """

    mesh: Mesh
    pspec: Any
    param_shardings: Any
    decode_step: Callable
    prefill_step: Callable
    init_pools: Callable


def build_paged_serve_steps(
    mc: ModelConfig, pc: ParallelConfig, mesh: Mesh, *, pcfg,
) -> PagedServeBundle:
    from repro.serve import kv_cache as KC
    from repro.serve import paged_model as PM

    rules = pier_rules(
        have_pod="pod" in mesh.axis_names, fsdp=pc.fsdp,
        shard_experts=pc.shard_experts, inside_manual=False,
        context_parallel_seq=pc.context_parallel,
        axis_sizes=M.axis_sizes(mesh))

    pshapes = _param_shapes(mc, scan_layers=False)  # paged path is unstacked
    pspec = S.param_specs(pshapes, mesh, pc)
    param_shardings = S.shardings(pspec, mesh)

    def decode(params, pools, tokens, positions, block_tables, context_lens):
        with use_rules(rules):
            return PM.paged_decode_step(
                params, mc, pools, tokens, positions, block_tables,
                context_lens, pcfg=pcfg)

    def prefill(params, tokens, pools, block_table, last_index):
        with use_rules(rules):
            logits, pools = PM.paged_prefill(
                params, mc, tokens, pools, block_table,
                pcfg=pcfg, use_pallas=pc.use_pallas)
            # serving semantics: only the last real token's logits leave
            # the step (``last_index`` skips the block-padding tail)
            last = jax.lax.dynamic_index_in_dim(logits, last_index, axis=1)
            return last[:, 0], pools

    def _with_mesh(fn):
        def call(*args, **kw):
            with compat.mesh_context(mesh):
                return fn(*args, **kw)
        return call

    decode_step = _with_mesh(jax.jit(decode, donate_argnums=(1,)))
    prefill_step = _with_mesh(jax.jit(prefill, donate_argnums=(2,)))
    init_pools = _with_mesh(jax.jit(lambda: KC.init_pools(mc, pcfg)))

    return PagedServeBundle(
        mesh=mesh, pspec=pspec, param_shardings=param_shardings,
        decode_step=decode_step, prefill_step=prefill_step,
        init_pools=init_pools)
