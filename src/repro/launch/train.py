"""Training launcher: ``python -m repro.launch.train --arch gpt2-small ...``

Runs the full Pier loop on whatever devices are available (CPU host devices
for validation, a real TPU slice in production — the code path is identical).
The host loop consults :class:`PierSchedule` each step: warmup (global
AdamW) -> momentum accumulation every r steps -> switch to group-local inner
steps -> outer Nesterov sync every r steps, with optional host offload of the
outer state between syncs (§V). With ``sync_delay > 0`` every boundary —
warmup accumulate and outer sync alike — is split into an async dispatch
(overlapping the next inner steps) and a delayed apply flowing through one
in-flight window; a sync controller can re-resolve the delay and switch the
sync strategy mid-run — see DESIGN.md §5/§9.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import time
from pathlib import Path
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.checkpoint import CheckpointManager
from repro.config import (MembershipConfig, ModelConfig, OuterCommConfig,
                          ParallelConfig, TrainConfig)
from repro.configs import get_config, get_reduced_config
from repro.core import offload
from repro.core.pier import PierSchedule
from repro.data.pipeline import synthetic_pipeline
from repro.kernels import backend as kbackend
from repro.launch import mesh as M
from repro.parallel.steps import build_train_steps
from repro.sync import (ChurnSchedule, MembershipController,
                        ModelDelayController, resolve_strategy)


# The checkout root (src/repro/launch/train.py -> the repo).
REPO_ROOT = Path(__file__).resolve().parents[3]

# The kernels a training step runs with ParallelConfig.use_pallas on.
TRAINED_KERNELS = ("flash_attention", "rmsnorm", "pier_update", "quantize",
                   "dequantize")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is the cache (jax reads it
    itself) and no other directory is set. Otherwise the cache is
    ``.jax_cache/`` at the checkout root: a fixed path, so that a second
    run finds what the first compiled.
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def pallas_kernels_compiled() -> bool:
    """Whether the resolved backend compiles every kernel a step runs.

    The launcher's ``use_pallas``: on a TPU the training step runs the
    compiled Pallas kernels; in the interpret and jnp-ref lanes it keeps
    the jnp paths.
    """
    return all(kbackend.kernel_lane(k) == kbackend.COMPILED
               for k in TRAINED_KERNELS)


def resolve_mesh_shape(mesh_arg: str, groups: Optional[int],
                       num_devices: int) -> Tuple[int, int, int]:
    """``(data_outer, data_inner, model)`` for the devices present.

    ``mesh_arg`` ("G,D,M") must use every device. Without it the groups
    split the devices evenly along ``data_inner``; ``groups`` defaults to
    2 where the device count is even and to 1 where it is odd (one chip).
    Raises ``ValueError`` with the reason when the request does not fit.
    """
    if mesh_arg:
        shape = tuple(int(x) for x in mesh_arg.split(","))
        if len(shape) != 3:
            raise ValueError(f"--mesh {mesh_arg!r} needs three sizes "
                             f"(data_outer,data_inner,model)")
        if shape[0] * shape[1] * shape[2] != num_devices:
            raise ValueError(f"--mesh {mesh_arg} spans "
                             f"{shape[0] * shape[1] * shape[2]} devices; "
                             f"this host has {num_devices}")
        return shape
    if groups is None:
        groups = 2 if num_devices % 2 == 0 else 1
    if groups < 1 or num_devices % groups:
        raise ValueError(f"--groups {groups} must divide the {num_devices} "
                         f"device(s) on this host: each group needs its own")
    return (groups, num_devices // groups, 1)


def resolve_auto_sync_delay(tc: TrainConfig, mc: ModelConfig,
                            pc: ParallelConfig, *, chip: str = "") -> int:
    """Resolve ``sync_delay="auto"`` to d* from the overlap step-time model.

    d* is the smallest delay that fully hides the outer collective given
    the mesh and a ``chip`` hint (benchmarks/overlap.py). Warns and falls
    back to 0 (eager) whenever the model has no estimate: no/unknown chip
    hint, or the benchmarks package not importable from this deployment.
    The Trainer itself goes further and *measures* t_comm/t_inner on-line
    (repro/sync/delay.py); this analytic resolution is the fallback and
    the standalone entry point.
    """
    if tc.sync_delay != "auto":
        return tc.sync_delay
    return ModelDelayController(tc, mc, pc, chip=chip).initial_delay()


_GC_SPANS = []  # the python.gc span of the collection under way


def _gc_span(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: one ``python.gc`` span per collector pass."""
    if phase == "start":
        span = TraceAnnotation("python.gc", generation=info["generation"])
        span.__enter__()
        _GC_SPANS.append(span)
    elif _GC_SPANS:
        _GC_SPANS.pop().__exit__(None, None, None)


def trace_gc_passes() -> None:
    """Show every garbage-collector pass as a ``python.gc`` span in a
    profiler trace (a host pause the device may wait on); installed once
    per process."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)


class Trainer:
    """Host-side training loop weaving inner/outer steps per the schedule.

    Every outer boundary — warmup accumulate and outer sync alike — flows
    through the same single in-flight dispatch/apply window (DESIGN.md
    §9). A :class:`~repro.sync.SyncController` (injected, or built from
    the strategy hook when ``sync_delay="auto"``) is consulted after
    every outer dispatch; its decisions re-resolve the overlap delay
    and/or *switch the sync strategy* mid-run — a switch flushes the
    window and swaps to a per-strategy cached :class:`StepBundle` (the
    re-jit boundary), retargeting the error-feedback residual when the
    residual requirement changes.
    """

    def __init__(self, mc: ModelConfig, tc: TrainConfig, pc: ParallelConfig,
                 mesh, *, checkpoint_dir: Optional[str] = None,
                 chip_hint: str = "", sync_controller=None,
                 adaptive_sync: bool = False, remeasure_every: int = 0,
                 membership=None):
        self.strategy = resolve_strategy(tc)
        # elastic membership (DESIGN.md §11): an injected
        # MembershipController (scripted churn), or one built from
        # tc.membership (full membership through the elastic graphs —
        # bit-identical to the fixed path at all-ones weights). Either
        # way tc.membership gates the elastic step variants in the bundle.
        if membership is not None:
            if membership.num_groups != pc.num_groups:
                raise ValueError(
                    f"membership controller tracks {membership.num_groups} "
                    f"groups but the mesh has {pc.num_groups}")
            if tc.membership is None:
                tc = tc.replace(membership=membership.cfg)
        elif tc.membership is not None:
            membership = MembershipController(
                pc.num_groups, cfg=tc.membership)
        self.membership = membership
        # sync_delay="auto": the strategy injects a SyncController —
        # measured t_comm/t_inner once enough sync windows are observed,
        # the analytic --chip model (or eager) until then; with
        # adaptive_sync the controller may also walk the strategy ladder.
        self.sync_controller = sync_controller
        if self.sync_controller is None and tc.sync_delay == "auto":
            self.sync_controller = self.strategy.make_sync_controller(
                tc, mc, pc, chip=chip_hint, adaptive=adaptive_sync,
                remeasure_every=remeasure_every)
        if tc.sync_delay == "auto":
            dec = self.sync_controller.initial_decision()
            if dec.strategy is not None and dec.strategy != self.strategy:
                self.strategy = dec.strategy
            tc = tc.replace(sync_delay=dec.clamped_delay(tc.sync_interval))
        self.mc, self.tc, self.pc = mc, tc, pc
        self.mesh = mesh
        trace_gc_passes()
        self.sched = PierSchedule(tc)
        # jitted step bundles are cached per strategy: a controller that
        # switches back to an earlier rung re-uses the compiled steps
        self._bundles = {}
        self.bundle = self._bundle_for(self.strategy)
        self.state = self.bundle.init_state(jax.random.PRNGKey(tc.seed))
        self.outer = self.bundle.init_outer(self.state)
        self.step = 0
        self.ckpt = (CheckpointManager(checkpoint_dir)
                     if checkpoint_dir else None)
        self._outer_on_host = False
        self.history = []
        # the host's run-ahead of one step: the metrics train_step
        # returned last, which the next call waits on; the calls that
        # waited, and those whose wait found that step still running
        self._last_metrics = None
        self.steps_waited = 0
        self.steps_ahead = 0
        # the (single) in-flight window, uniform over ops (DESIGN.md §9):
        # (apply_at, "outer", DispatchState | [ChunkDispatch]) or
        # (apply_at, "accumulate", pending OuterState).
        # sync_delay < sync_interval bounds the queue depth at one.
        self._inflight = None
        # the EventMembership record bound to an in-flight *outer*
        # dispatch (None when no membership / accumulate): consumed by
        # its apply for the live mask and the post-apply bootstraps
        self._inflight_member = None
        if tc.offload_outer_state:
            self.outer = offload.to_host(self.outer)
            self._outer_on_host = True

    @property
    def delay_controller(self):
        """Back-compat view: the scalar-delay half of the sync controller
        (None when no controller is installed)."""
        c = self.sync_controller
        return c.delay_controller if c is not None else None

    def _bundle_for(self, strategy):
        b = self._bundles.get(strategy)
        if b is None:
            b = build_train_steps(self.mc, self.tc, self.pc, self.mesh,
                                  strategy=strategy)
            self._bundles[strategy] = b
        return b

    # ------------------------------------------------------------------
    def _outer_to_device(self):
        if self._outer_on_host:
            self.outer = offload.to_device(self.outer)
            self._outer_on_host = False

    def _outer_to_host(self):
        if self.tc.offload_outer_state and not self._outer_on_host:
            self.outer = offload.to_host(self.outer)
            self._outer_on_host = True

    def train_step(self, batch) -> dict:
        """One scheduled step (inner or warmup + its outer events).

        With ``sync_delay == 0`` the dispatch+apply pair that fires at a
        sync boundary is fused into the classic eager ``outer_step`` /
        ``accumulate_step`` — the pre-delay code paths, bit for bit. With
        ``sync_delay > 0`` dispatch enqueues the event's computation
        without blocking the host (jax dispatch is async — no
        ``block_until_ready`` anywhere on this path), so it overlaps the
        next ``sync_delay`` inner steps; apply then installs the result —
        the target with the stale-delta correction for outer events, the
        pending outer state for warmup accumulates.

        Returns this step's metrics (``loss``, ``grad_norm``, ``lr``) as
        the device scalars the jitted step produced, not read to the host.
        The host runs at most one step ahead of the device: after
        enqueueing step n the call waits until step n-1's metrics are
        ready, so the next batch's transfer and dispatch overlap step n's
        compute, and a returned step n-1 is finished on the device.

        Under a running profiler the step records three host spans, each
        with the step number: ``trainer.dispatch`` until the inner or
        warmup step is enqueued, ``trainer.outer`` around the step's outer
        events (only on a step that has any), and ``trainer.metrics_read``
        where the host waits for the previous step's metrics; its
        ``ahead`` is 1 when that step was still running as the wait
        began (counted in ``steps_ahead`` of ``steps_waited``).
        """
        step = self.step
        with TraceAnnotation("trainer.dispatch", step=step):
            phase = self.sched.phase(step)
            step_arr = jnp.asarray(step, jnp.int32)
            t0 = time.perf_counter()
            if phase == "warmup":
                self.state, metrics = self.bundle.warmup_step(
                    self.state, batch, step_arr)
            else:
                self.state, metrics = self.bundle.inner_step(
                    self.state, batch, step_arr)
        ctrl = self.sync_controller
        if ctrl is not None and ctrl.wants_measurement:
            # materializing the metrics blocks on the inner step — the
            # wall time is the measured t_inner fed to the controller.
            # Outside the measurement windows the host only waits for the
            # previous step, off the dispatch-enqueue critical path.
            with TraceAnnotation("trainer.metrics_read", step=step):
                jax.device_get(metrics)
            ctrl.observe_step(time.perf_counter() - t0)
        events = self.sched.events(step)
        if events or self._inflight is not None:
            with TraceAnnotation("trainer.outer", step=step):
                self._outer_events(step, events)
        self.step += 1
        prev, self._last_metrics = self._last_metrics, metrics
        if prev is not None:
            ahead = not all(v.is_ready() for v in prev.values())
            with TraceAnnotation("trainer.metrics_read", step=step,
                                 ahead=int(ahead)):
                jax.block_until_ready(prev)
            self.steps_waited += 1
            self.steps_ahead += ahead
        return metrics

    def _outer_events(self, step: int, events):
        """The outer events of ``step``: dispatches, applies, the fused
        eager outer step, and the controller round that follows them."""
        sched = self.sched
        ctrl = self.sync_controller
        chunked = self.bundle.chunk_dispatch_steps is not None
        # while the controller still wants t_comm samples the sync must go
        # through dispatch/apply (bit-identical at d=0); once measurement
        # is done a resolved d*=0 takes the fused eager step
        measuring = ctrl is not None and ctrl.wants_measurement
        fused_outer = any(ev.kind == "dispatch" and ev.op == "outer"
                          and ev.apply_step == step for ev in events)
        if fused_outer and not chunked and not measuring:
            # a delay re-resolution to 0 can leave the last measured
            # window's dispatch in flight — install it before the eager step
            self._apply_inflight()
            self._outer_to_device()
            if self.membership is not None:
                rec = self.membership.at(sched.outer_index(step))
                self.state, self.outer = self.bundle.elastic_outer_step(
                    self.state, self.outer,
                    jnp.float32(sched.mu_at(step)),
                    jnp.float32(sched.outer_lr_at(step)),
                    jnp.asarray(rec.weights, jnp.float32),
                    jnp.asarray(rec.apply_live))
                self._bootstrap_groups(rec.bootstrap_after_apply)
            else:
                self.state, self.outer = self.bundle.outer_step(
                    self.state, self.outer,
                    jnp.float32(sched.mu_at(step)),
                    jnp.float32(sched.outer_lr_at(step)))
            self._outer_to_host()
            self._consult_controller()
        else:
            for ev in events:
                if ev.kind == "apply":
                    # the stored apply_step is authoritative: a delay
                    # decision adopted mid-window rebuilds the schedule,
                    # whose re-timed apply event must not cut the
                    # already-dispatched window short
                    if (self._inflight is not None
                            and self._inflight[0] <= step):
                        self._apply_inflight()
                    continue
                # a delay re-resolution may have shrunk the window to
                # nothing — never strand (or double-book) an in-flight
                # dispatch
                self._apply_inflight()
                if ev.op == "accumulate":
                    self._dispatch_accumulate(ev)
                else:
                    dispatch = self._dispatch(step)
                    self._inflight = (ev.apply_step, "outer", dispatch)
                    self._consult_controller()
            # a delay decision can shrink a window below its dispatched
            # length — never let a due apply slip past its step
            if self._inflight is not None and self._inflight[0] <= step:
                self._apply_inflight()

    def _dispatch_accumulate(self, ev):
        """Warmup accumulate as a dispatch/apply pair (DESIGN.md §9).

        Eager (``apply_step == sync_step``): the donating
        ``accumulate_step`` — the pre-delay path, bit for bit. Delayed:
        the non-donating dispatch computes the pending outer state from
        the dispatch-time params; the pre-dispatch state stays live until
        the apply installs the result (whose stale-delta correction is
        identically zero — ``core.outer.warmup_apply``).

        While a measured controller still wants t_comm samples, the
        warmup accumulate windows are wall-clocked too: the accumulate's
        global reduce moves the full-precision Δθ tree, so for an fp32
        strategy its timing is directly representative, and for a
        compressed wire the controller rescales the sample by the modeled
        payload-width ratio (``warmup=True`` →
        :attr:`~repro.sync.delay.MeasuredDelayController.warmup_scale`) —
        either way d* resolves *before* the first post-warmup sync
        instead of burning the first real windows on measurement.
        """
        mu = jnp.float32(self.sched.mu_at(ev.sync_step))
        ctrl = self.sync_controller
        measure = ctrl is not None and ctrl.wants_measurement
        t0 = time.perf_counter() if measure else 0.0
        self._outer_to_device()
        if ev.apply_step <= ev.sync_step:
            self.outer = self.bundle.accumulate_step(
                self.state, self.outer, mu)
            if measure:
                jax.block_until_ready(self.outer.momentum)
            self._outer_to_host()
        else:
            pending = self.bundle.accumulate_dispatch_step(
                self.state, self.outer, mu)
            if measure:
                # overlap is sacrificed for the measured windows only —
                # the same policy the outer dispatch measurement applies
                jax.block_until_ready(pending.momentum)
            self._inflight = (ev.apply_step, "accumulate", pending)
            # the old outer state stays current for the window but is
            # never read again before the apply replaces it wholesale —
            # offload (when configured) can evict it right away instead
            # of holding 2x the outer state on device for d steps
            self._outer_to_host()
        if measure:
            ctrl.observe_window(t_comm=time.perf_counter() - t0,
                                warmup=True)
            # adopt a freshly resolved d* right away (delay only — no
            # tick: strategy decisions stay keyed on *outer* windows, so
            # scripted replays are unaffected by warmup sampling)
            self._adopt_delay(ctrl.current_decision())

    def _dispatch(self, step: int):
        """Launch the outer collective for the sync boundary at ``step``.

        With a chunked strategy plan the Δθ leaf spans dispatch as
        separate XLA computations enqueued back to back (none blocks the
        host), so chunk k's cross-domain reduce overlaps chunk k+1's
        quantization; each chunk carries its own ChunkDispatch, so the
        per-chunk applies later install early chunks while late chunks'
        collectives are still in flight.

        While the controller is measuring, the host blocks on the
        dispatched targets to wall-clock t_comm (overlap is sacrificed for
        those windows only); the decision round itself runs afterwards in
        ``_consult_controller``.
        """
        sched = self.sched
        mu = jnp.float32(sched.mu_at(step))
        olr = jnp.float32(sched.outer_lr_at(step))
        ctrl = self.sync_controller
        measure = ctrl is not None and ctrl.wants_measurement
        t0 = time.perf_counter() if measure else 0.0
        self._outer_to_device()
        if self.bundle.chunk_dispatch_steps is not None:
            chunks, chunk_leaves = [], []
            for chunk_step in self.bundle.chunk_dispatch_steps:
                chunk, leaves = chunk_step(self.state, self.outer, mu, olr)
                chunks.append(chunk)
                chunk_leaves.append(leaves)
            self.outer = self.bundle.stitch_outer(self.outer, chunk_leaves)
            dispatch = chunks  # a list marks the per-chunk in-flight shape
        elif self.membership is not None:
            rec = self.membership.at(sched.outer_index(step))
            dispatch, self.outer = self.bundle.elastic_dispatch_step(
                self.state, self.outer, mu, olr,
                jnp.asarray(rec.weights, jnp.float32))
            self._inflight_member = rec
        else:
            dispatch, self.outer = self.bundle.dispatch_step(
                self.state, self.outer, mu, olr)
        self._outer_to_host()
        if measure:
            jax.block_until_ready(
                [c.targets for c in dispatch] if isinstance(dispatch, list)
                else dispatch.target)
            ctrl.observe_window(t_comm=time.perf_counter() - t0)
        return dispatch

    def _consult_controller(self):
        """One decision round after an outer sync window.

        Ticks the window (feeding ``remeasure_every`` counters), then
        adopts the decision: a strategy switch first (it flushes the
        window just dispatched through the *old* bundle before swapping),
        then the clamped delay for the following windows.
        """
        ctrl = self.sync_controller
        if ctrl is None:
            return
        ctrl.tick_window()
        dec = ctrl.current_decision()
        if dec.strategy is not None and dec.strategy != self.strategy:
            self._switch_strategy(dec.strategy)
        self._adopt_delay(dec)

    def _adopt_delay(self, dec):
        """Adopt a decision's clamped delay (rebuilding the schedule)."""
        d = dec.clamped_delay(self.tc.sync_interval)
        if d != self.tc.sync_delay:
            print(f"sync_delay re-resolved: {self.tc.sync_delay} -> {d} "
                  f"({type(self.sync_controller).__name__} decision)",
                  flush=True)
            self.tc = self.tc.replace(sync_delay=d)
            self.sched = PierSchedule(self.tc)

    def _switch_strategy(self, strategy):
        """Adopt a new outer-sync strategy mid-run (DESIGN.md §9).

        The in-flight window is flushed through the old bundle (its
        payload was produced by the old strategy's jitted steps), the
        per-strategy cached bundle is swapped in (the re-jit boundary),
        and the error-feedback residual is retargeted: materialized at
        zero when the new plan needs one the state lacks, dropped when it
        does not. Momentum/anchor/num_syncs carry over untouched.
        """
        self.flush()
        print(f"outer-sync strategy switch: {self.strategy.name} -> "
              f"{strategy.name}", flush=True)
        self.strategy = strategy
        self.bundle = self._bundle_for(strategy)
        self._outer_to_device()
        need = self.bundle.plan.needs_residual
        if need and self.outer.residual is None:
            self.outer = self.outer._replace(
                residual=self.bundle.init_residual(self.state))
        elif not need and self.outer.residual is not None:
            self.outer = self.outer._replace(residual=None)
        # the rs-ag wire path's second residual retargets the same way
        # (init_residual's zero tree has the right stacked shardings)
        need2 = getattr(self.bundle.plan, "needs_residual2", False)
        if need2 and getattr(self.outer, "residual2", None) is None:
            self.outer = self.outer._replace(
                residual2=self.bundle.init_residual(self.state))
        elif not need2 and getattr(self.outer, "residual2", None) is not None:
            self.outer = self.outer._replace(residual2=None)
        self._outer_to_host()

    def _apply_inflight(self):
        # The schedule emits apply events purely by step count; if flush()
        # already drained the window (checkpoint mid-flight, segmented
        # run()), the event is a no-op rather than a double apply.
        if self._inflight is None:
            return
        _, op, payload = self._inflight
        rec, self._inflight_member = self._inflight_member, None
        if op == "accumulate":
            # install the pending outer state (core.outer.warmup_apply —
            # the warmup stale-delta correction is identically zero)
            self.outer = payload
            self._outer_on_host = False
            self._outer_to_host()
        elif isinstance(payload, list):  # per-chunk apply, span order
            for chunk, apply_step in zip(payload,
                                         self.bundle.chunk_apply_steps):
                self.state = apply_step(self.state, chunk)
        elif rec is not None:
            # elastic apply (DESIGN.md §11): only live groups install the
            # target; then the groups rejoining at the next event
            # bootstrap off the freshly installed anchor (or checkpoint)
            self._inflight = None
            self.state = self.bundle.elastic_apply_step(
                self.state, payload, jnp.asarray(rec.apply_live))
            self._bootstrap_groups(rec.bootstrap_after_apply)
            return
        else:
            self.state = self.bundle.apply_step(self.state, payload)
        self._inflight = None

    def _bootstrap_groups(self, groups):
        """Rejoin bootstrap (DESIGN.md §11), right after an event's apply.

        Each named group's replica is reset to the donor params — the
        freshly installed anchor (exact: the applied target *is* the new
        anchor), or the latest complete checkpoint's anchor when
        ``rejoin_bootstrap="checkpoint"`` — with fresh inner-opt state and
        a zeroed error-feedback residual, so it trains the next window
        coherently and re-enters the mask at the next dispatch boundary.
        """
        if not groups:
            return
        self._outer_to_device()
        donor = self._bootstrap_donor()
        for g in groups:
            self.state, self.outer = self.bundle.bootstrap_group(
                self.state, self.outer, jnp.asarray(g, jnp.int32), donor)
        self._outer_to_host()

    def _bootstrap_donor(self):
        cfg = self.tc.membership
        if (cfg is not None and cfg.rejoin_bootstrap == "checkpoint"
                and self.ckpt is not None):
            latest = self.ckpt.latest_step()
            if latest is not None:
                trees, _ = self.ckpt.restore(latest, {"outer": self.outer})
                return trees["outer"].anchor
        return self.outer.anchor

    def flush(self):
        """Drain an in-flight dispatch (end of run / before checkpoint)."""
        if self._inflight is not None:
            self._apply_inflight()

    def run(self, steps: int, pipeline, *, log_every: int = 10,
            ckpt_every: int = 0):
        """Train ``steps`` steps from ``pipeline``; returns ``history``,
        every step's metrics so far as Python floats.

        The loop keeps ``train_step``'s run-ahead of one step: it reads
        metrics to the host only at the ``log_every`` steps, and all of
        the history in one transfer at the end.
        """
        t0 = time.time()
        for _ in range(steps):
            batch = next(pipeline)
            metrics = self.train_step(batch)
            self.history.append(metrics)
            if log_every and self.step % log_every == 0:
                m = jax.device_get(metrics)
                dt = (time.time() - t0) / max(self.step, 1)
                print(f"step {self.step:6d} loss {m['loss']:.4f} "
                      f"lr {m['lr']:.2e} gnorm {m['grad_norm']:.3f} "
                      f"({dt*1e3:.0f} ms/step avg)", flush=True)
            if ckpt_every and self.ckpt and self.step % ckpt_every == 0:
                self.save()
        self.flush()
        self.history = jax.tree.map(float, jax.device_get(self.history))
        return self.history

    def save(self):
        self.flush()  # a checkpoint must not strand an in-flight dispatch
        self._outer_to_device()
        self.ckpt.save(self.step, {"state": self.state, "outer": self.outer},
                       metadata={"step": self.step,
                                 "optimizer": self.tc.optimizer})
        self._outer_to_host()

    def restore(self, step: Optional[int] = None):
        step = step if step is not None else self.ckpt.latest_step()
        self._outer_to_device()
        trees, meta = self.ckpt.restore(
            step, {"state": self.state, "outer": self.outer},
            shardings={
                "state": jax.tree.map(lambda x: x.sharding, self.state),
                "outer": jax.tree.map(lambda x: x.sharding, self.outer),
            })
        self.state, self.outer = trees["state"], trees["outer"]
        self.step = meta["step"]
        self._inflight = None  # checkpoints are saved flushed
        self._outer_to_host()


def main(argv=None):
    ap = argparse.ArgumentParser(description="Pier training launcher")
    ap.add_argument("--arch", default="gpt2-small")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced smoke-scale config")
    ap.add_argument("--optimizer", default="pier",
                    choices=["pier", "diloco", "adamw"])
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--total-steps", type=int, default=0,
                    help="schedule horizon (defaults to --steps)")
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--sync-interval", type=int, default=10)
    ap.add_argument("--sync-delay", default="0",
                    help="overlap the outer all-reduce with this many "
                         "inner steps (0 = eager; 'auto' = resolve d* from "
                         "the overlap step-time model, needs --chip)")
    ap.add_argument("--chip", default="",
                    help="chip hint for --sync-delay auto "
                         "(e.g. tpu-v5e, a100-perlmutter, gh200-vista)")
    ap.add_argument("--adaptive-sync", action="store_true",
                    help="with --sync-delay auto: let the controller also "
                         "switch the sync strategy down its ladder when "
                         "the measured t_comm stays exposed at the max "
                         "legal delay (DESIGN.md §9)")
    ap.add_argument("--remeasure-every", type=int, default=0,
                    help="re-sample t_comm/t_inner every N sync windows "
                         "after the initial measurement (0 = measure once)")
    ap.add_argument("--outer-compression", default="none",
                    choices=["none", "quantize", "int8-wire", "rs-ag"],
                    help="compress the cross-pod Δθ payload (int8-wire: "
                         "ring-exchange the actual packed q+scales; "
                         "rs-ag: quantized reduce-scatter + all-gather, "
                         "~2/E of the per-device wire bytes)")
    ap.add_argument("--outer-comm-bits", type=int, default=8,
                    choices=[4, 8])
    ap.add_argument("--hierarchical-reduce", action="store_true",
                    help="two-stage outer reduce: fp32 intra-pod, "
                         "compressed cross-pod")
    ap.add_argument("--comm-chunks", type=int, default=1,
                    help="dispatch the Δθ tree as this many separate "
                         "XLA computations")
    ap.add_argument("--sharded-outer", action="store_true",
                    help="exchange only each device's Δθ shard along the "
                         "auto (TP/FSDP) axes, with the outer state "
                         "sharded alongside (DESIGN.md §10)")
    ap.add_argument("--churn-script", default="",
                    help="scripted elastic membership (DESIGN.md §11), "
                         "e.g. 'drop:1@3,rejoin:1@6,straggle:0@4+2' — "
                         "entries keyed on the post-warmup outer event "
                         "ordinal; empty = full membership")
    ap.add_argument("--max-staleness", type=int, default=1,
                    help="straggler tolerance: a group more than this "
                         "many missed outer events behind is evicted "
                         "from the apply cohort until it bootstraps back")
    ap.add_argument("--min-live", type=int, default=1,
                    help="fail fast if the churn script ever leaves "
                         "fewer contributing groups than this")
    ap.add_argument("--rejoin-bootstrap", default="anchor",
                    choices=["anchor", "checkpoint"],
                    help="donor for a rejoining group's params: the "
                         "freshly installed anchor, or the latest "
                         "complete checkpoint (needs --checkpoint-dir)")
    ap.add_argument("--groups", type=int, default=None,
                    help="Pier groups (data_outer); default 2 on an even "
                         "device count, else 1")
    ap.add_argument("--mesh", default="",
                    help="mesh shape e.g. 2,2,2 = data_outer,data_inner,model"
                         " (default: --groups groups, each over an equal "
                         "share of the devices along data_inner)")
    ap.add_argument("--lr", type=float, default=4e-4)
    ap.add_argument("--offload", action="store_true")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kernel-backend", default="",
                    choices=["", "auto", "tpu-mosaic", "gpu-triton",
                             "interpret", "jnp-ref"],
                    help="force the kernel lowering lane "
                         "(kernels/backend.py registry; default: "
                         "REPRO_KERNEL_BACKEND env var or platform "
                         "auto-detect) and apply its environment preset "
                         "before first device use")
    args = ap.parse_args(argv)
    if ((args.adaptive_sync or args.remeasure_every)
            and args.sync_delay != "auto"):
        ap.error("--adaptive-sync/--remeasure-every need --sync-delay auto "
                 "(the measured controller they configure only runs there)")

    if args.kernel_backend and args.kernel_backend != "auto":
        # env preset before the jax.device_count() below triggers backend
        # init (XLA_FLAGS is read exactly once, at init) — append-only,
        # so CI's pre-set --xla_force_host_platform_device_count survives
        preset = M.apply_env_preset(args.kernel_backend)
        if preset["xla_flags_appended"]:
            print("env preset "
                  f"({args.kernel_backend}): appended XLA_FLAGS "
                  + " ".join(preset["xla_flags_appended"]))
        if preset["ld_preload_hint"]:
            print(f"env preset ({args.kernel_backend}): tcmalloc available "
                  f"at {preset['ld_preload_hint']} — export LD_PRELOAD in "
                  f"the wrapper script to use it")
    kbackend.set_kernel_backend(args.kernel_backend or None)

    mc = (get_reduced_config(args.arch) if args.reduced
          else get_config(args.arch))
    try:
        shape = resolve_mesh_shape(args.mesh, args.groups,
                                   jax.device_count())
    except ValueError as e:
        ap.error(str(e))
    use_compile_cache()
    mesh = M.small_mesh(shape, ("data_outer", "data_inner", "model"))
    pc = ParallelConfig(
        data_axis_size=shape[0] * shape[1], model_axis_size=shape[2],
        data_outer=shape[0], use_pallas=pallas_kernels_compiled())
    sync_delay = (args.sync_delay if args.sync_delay == "auto"
                  else int(args.sync_delay))
    tc = TrainConfig(
        optimizer=args.optimizer,
        total_steps=args.total_steps or args.steps,
        global_batch_size=args.global_batch,
        seq_len=args.seq_len,
        sync_interval=args.sync_interval,
        sync_delay=sync_delay,
        inner_lr=args.lr, inner_min_lr=args.lr / 10,
        offload_outer_state=args.offload,
        seed=args.seed,
        lazy_start=args.optimizer != "diloco",
        outer_comm=OuterCommConfig(
            compression=args.outer_compression,
            bits=args.outer_comm_bits,
            hierarchical=args.hierarchical_reduce,
            chunks=args.comm_chunks,
            sharded=args.sharded_outer),
    )
    membership = None
    if args.churn_script:
        mcfg = MembershipConfig(max_staleness=args.max_staleness,
                                min_live=args.min_live,
                                rejoin_bootstrap=args.rejoin_bootstrap)
        tc = tc.replace(membership=mcfg)
        membership = MembershipController(
            pc.num_groups, cfg=mcfg,
            schedule=ChurnSchedule.parse(args.churn_script))
    strategy = resolve_strategy(tc)
    # Pallas kernels cannot be partitioned over the in-group axes: they
    # run replicated there (kernels/backend.py:mosaic_call) — say so
    replicated = (kbackend.kernel_replicated_axes(mesh, M.manual_axes(mesh))
                  if pc.use_pallas else ())
    print(f"arch={mc.name} optimizer={tc.optimizer} mesh={shape} "
          f"groups={pc.num_groups} devices={jax.device_count()} "
          f"outer_sync={strategy.name} "
          f"kernel_backend={kbackend.resolve_backend().name} "
          f"use_pallas={pc.use_pallas} "
          f"transport={strategy.transport_name(mesh)}"
          + (f" churn={args.churn_script}" if args.churn_script else "")
          + (f" pallas_kernels_replicated_over={','.join(replicated)}"
             if replicated else ""))
    trainer = Trainer(mc, tc, pc, mesh,
                      checkpoint_dir=args.checkpoint_dir or None,
                      chip_hint=args.chip,
                      adaptive_sync=args.adaptive_sync,
                      remeasure_every=args.remeasure_every,
                      membership=membership)
    if tc.sync_delay == "auto":
        print(f"sync_delay=auto resolved to d*={trainer.tc.sync_delay} "
              f"(chip={args.chip or 'none'}; re-resolves from measured "
              f"sync windows)")
    pipeline = synthetic_pipeline(mesh, M.data_axes(mesh), mc, trainer.tc)
    try:
        trainer.run(args.steps, pipeline, log_every=args.log_every,
                    ckpt_every=args.ckpt_every)
    finally:
        pipeline.close()
    print(json.dumps({"final_loss": trainer.history[-1]["loss"],
                      "steps": trainer.step}))


if __name__ == "__main__":
    main()
