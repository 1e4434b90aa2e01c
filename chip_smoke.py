"""Smoke run of the Pier trainer on a TPU: ``python chip_smoke.py``.

One chip, one process (the default):

1. Every Pallas kernel of the main path, compiled for the chip, against its
   ``kernels/ref.py`` oracle at gpt2-small widths (tolerances below).
2. The Trainer (``repro.launch.train``) on gpt2-small at its published
   width (12 layers, d_model 768, 12 heads, vocab 50304), seq_len 1024,
   global batch 8, one group, ``use_pallas`` as the launcher sets it:
   warmup, inner steps and outer syncs every 4 steps, once with the flat
   fp32 exchange and once with ``int8-wire``. Every loss must be finite,
   the last below the first, and the compiled inner and outer steps must
   hold a Mosaic kernel (``tpu_custom_call``).

``--four-chips`` runs only the exchange across chips: the same model on a
``4,1,1`` mesh (four groups, one per chip) with fp32 and int8-wire, and
``--optimizer adamw`` (an all-reduce every step) as the baseline. After
every outer sync the four chips must hold bitwise-identical parameters.

Earlier lines report the device, ms/step after the first steps (timed to
``block_until_ready``), peak device memory and compile times. The last line
is ``{"ok": true, "device": {...}}``. Without a TPU, or outside a checkout,
the script exits non-zero and prints no result line.
"""

from __future__ import annotations

import argparse
import json
import math
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# gpt2-small widths (src/repro/configs/gpt2_small.py)
D, HEADS, HEAD_DIM, FF, SEQ, BATCH = 768, 12, 64, 3072, 1024, 8
STEPS, SYNC_INTERVAL, WARMUP_FRAC = 16, 4, 0.25  # 4 warmup steps, 3 syncs

# Kernel-vs-oracle tolerances on the chip. The oracles run under
# matmul precision "highest"; the kernels' matmuls use the MXU's default
# passes, hence the attention bounds. The elementwise kernels are fp32.
TOL = {
    "flash_attention_fp32": 2e-2,   # max |out - ref|, outputs are O(1)
    "flash_attention_bf16": 6e-2,
    "decode_attention_bf16": 6e-2,
    "pier_update": 1e-6,            # max |out - ref| / (1 + |ref|)
    "rmsnorm": 1e-5,
    "quantize_q_mismatch": 1e-3,    # share of int8 codes off by one
}


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# phase 1: kernels against their oracles
# ---------------------------------------------------------------------------


def kernel_parity() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.decode_attention import (paged_decode_attention,
                                                paged_decode_attention_ref)
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.pier_update import pier_update
    from repro.kernels.quantize import (dequantize_blockwise,
                                        quantize_blockwise)
    from repro.kernels.rmsnorm import rmsnorm

    keys = iter(jax.random.split(jax.random.PRNGKey(0), 64))
    normal = lambda shape, dtype=jnp.float32: jax.random.normal(
        next(keys), shape, jnp.float32).astype(dtype)
    out = {}

    def max_err(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        check(np.isfinite(a).all(), "kernel output not finite")
        return float(np.abs(a - b).max())

    with jax.default_matmul_precision("highest"):
        for dtype, name in ((jnp.float32, "fp32"), (jnp.bfloat16, "bf16")):
            q, k, v = (normal((1, SEQ, HEADS, HEAD_DIM), dtype)
                       for _ in range(3))
            got = jax.jit(lambda q, k, v: flash_attention(
                q, k, v, interpret=False))(q, k, v)
            want = jax.jit(ref.flash_attention_ref)(q, k, v)
            out[f"flash_attention_{name}"] = max_err(got, want)

        B, N, BS, T = 8, 64, 16, 8
        qd = normal((B, HEADS, HEAD_DIM), jnp.bfloat16)
        kp = normal((N, BS, HEADS, HEAD_DIM), jnp.bfloat16)
        vp = normal((N, BS, HEADS, HEAD_DIM), jnp.bfloat16)
        tables = jax.random.permutation(next(keys), N)[:B * T].reshape(B, T)
        lens = jnp.arange(1, B + 1, dtype=jnp.int32) * (BS * T // B)
        got = jax.jit(lambda *a: paged_decode_attention(
            *a, interpret=False))(qd, kp, vp, tables, lens)
        want = jax.jit(paged_decode_attention_ref)(qd, kp, vp, tables, lens)
        out["decode_attention_bf16"] = max_err(got, want)

    worst = 0.0
    for n in (D * FF, D):  # a flat 2.4M-element leaf and a ragged one
        a, m, d = normal((n,)), normal((n,)), normal((n,))
        mu, lr = jnp.float32(0.9), jnp.float32(0.7)
        p, mm = jax.jit(lambda *t: pier_update(*t, interpret=False))(
            a, m, d, mu, lr)
        p_ref, m_ref = jax.jit(lambda a, m, d: ref.pier_update_ref(
            a, m, d, mu=mu, lr=lr))(a, m, d)
        for got, want in ((p, p_ref), (mm, m_ref)):
            want = np.asarray(want, np.float32)
            worst = max(worst, max_err(got, want)
                        / (1.0 + float(np.abs(want).max())))
    out["pier_update"] = worst

    x, s = normal((8 * SEQ, D)), 1.0 + 0.1 * normal((D,))
    got = jax.jit(lambda x, s: rmsnorm(x, s, interpret=False))(x, s)
    out["rmsnorm"] = max_err(got, jax.jit(ref.rmsnorm_ref)(x, s))

    x = normal((D * FF + 5,))
    q, sc = jax.jit(lambda x: quantize_blockwise(x, interpret=False))(x)
    q_ref, sc_ref = jax.jit(ref.quantize_blockwise_ref)(x)
    dq = np.asarray(q, np.int32) - np.asarray(q_ref, np.int32)
    check(np.abs(dq).max() <= 1, "quantize: an int8 code is off by more "
          "than one")
    check(np.allclose(np.asarray(sc), np.asarray(sc_ref), rtol=1e-6,
                      atol=0), "quantize: scales differ from the oracle")
    out["quantize_q_mismatch"] = float((dq != 0).mean())
    deq = jax.jit(lambda q, s: dequantize_blockwise(q, s, interpret=False))(
        q_ref, sc_ref)
    check(np.array_equal(np.asarray(deq),
                         np.asarray(ref.dequantize_blockwise_ref(q_ref,
                                                                 sc_ref))),
          "dequantize: not bitwise equal to the oracle")

    for name, err in out.items():
        log(f"kernel {name}: {err:.3e} (limit {TOL[name]:.0e})")
        check(err <= TOL[name], f"kernel {name}: {err} > {TOL[name]}")
    log("kernel dequantize: bitwise equal to the oracle")
    return out


# ---------------------------------------------------------------------------
# phase 2: the Trainer
# ---------------------------------------------------------------------------


def _groups_agree(params, num_groups: int) -> None:
    """Every group's replica is bitwise equal, one group on each device."""
    import jax
    import numpy as np

    for leaf in jax.tree.leaves(params):
        shards = sorted(leaf.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        devices = {s.device for s in shards}
        check(len(devices) == num_groups,
              f"a parameter sits on {len(devices)} device(s), not "
              f"{num_groups}")
        first = np.asarray(shards[0].data)
        for s in shards[1:]:
            check(np.array_equal(np.asarray(s.data), first),
                  f"groups differ after an outer sync on {s.device}")


def train(label: str, mc, *, mesh_shape, compression="none",
          optimizer="pier", use_pallas=True, require_mosaic=True) -> dict:
    import jax
    import numpy as np

    from repro.config import OuterCommConfig, ParallelConfig, TrainConfig
    from repro.data.pipeline import synthetic_pipeline
    from repro.launch import mesh as M
    from repro.launch.train import Trainer

    groups = mesh_shape[0]
    mesh = M.small_mesh(mesh_shape, ("data_outer", "data_inner", "model"))
    pc = ParallelConfig(
        data_axis_size=mesh_shape[0] * mesh_shape[1],
        model_axis_size=mesh_shape[2], data_outer=groups,
        use_pallas=use_pallas)
    tc = TrainConfig(
        optimizer=optimizer, total_steps=STEPS, global_batch_size=BATCH,
        seq_len=SEQ, sync_interval=SYNC_INTERVAL, sync_delay=0,
        warmup_frac=WARMUP_FRAC, inner_lr=4e-4, inner_min_lr=4e-5, seed=0,
        lazy_start=optimizer != "diloco",
        outer_comm=OuterCommConfig(compression=compression))
    t0 = time.perf_counter()
    trainer = Trainer(mc, tc, pc, mesh)
    jax.block_until_ready(trainer.state)
    setup_s = time.perf_counter() - t0
    sched = trainer.sched
    pipeline = synthetic_pipeline(mesh, M.data_axes(mesh), mc, trainer.tc)
    losses, step_s, phases = [], [], []
    try:
        batch = next(pipeline)
        # Compile the steps ahead, on the live arguments: the run then reads
        # them back from the compilation cache, and their HLO must hold a
        # Mosaic kernel.
        f32, step0 = jax.numpy.float32, jax.numpy.zeros((), jax.numpy.int32)
        steps = ({"warmup": (trainer.bundle.warmup_step,
                             (trainer.state, batch, step0))}
                 if optimizer == "adamw" else
                 {"inner": (trainer.bundle.inner_step,
                            (trainer.state, batch, step0)),
                  "outer": (trainer.bundle.outer_step,
                            (trainer.state, trainer.outer, f32(0.9),
                             f32(1.0)))})
        kernels, compile_s = {}, {}
        for name, (fn, args) in steps.items():
            t0 = time.perf_counter()
            hlo = fn.lower(*args).compile().as_text()
            compile_s[name] = time.perf_counter() - t0
            kernels[name] = hlo.count("tpu_custom_call")
            if require_mosaic:
                check(kernels[name] > 0,
                      f"{label}: compiled {name} step holds no Mosaic kernel")
        for step in range(STEPS):
            if step:
                batch = next(pipeline)
            phase = sched.phase(step)
            synced = sched.is_sync_step(step) and phase == "inner"
            t0 = time.perf_counter()
            metrics = trainer.train_step(batch)
            jax.block_until_ready((trainer.state, trainer.outer))
            step_s.append(time.perf_counter() - t0)
            losses.append(float(metrics["loss"]))
            phases.append("sync" if synced else phase)
            if groups > 1 and (synced or optimizer == "adamw"):
                _groups_agree(trainer.state.params, groups)
        trainer.flush()
    finally:
        pipeline.close()

    check(all(math.isfinite(x) for x in losses), f"{label}: loss not finite")
    check(losses[-1] < losses[0],
          f"{label}: loss did not fall ({losses[0]} -> {losses[-1]})")
    if optimizer != "adamw":  # adamw all-reduces every step instead
        check(phases.count("sync") >= 2,
              f"{label}: fewer than two outer syncs")


    # steady state: inner (or adamw) steps after the first of their kind
    steady = [t for i, (t, p) in enumerate(zip(step_s, phases))
              if p in ("inner", "warmup") and i > 0 and phases[i - 1] == p]
    syncs = [t for i, (t, p) in enumerate(zip(step_s, phases))
             if p == "sync" and "sync" in phases[:i]]
    ms = 1e3 * statistics.median(steady)
    tokens_s = BATCH * SEQ / (ms / 1e3)
    result = {
        "run": label, "losses": [round(x, 4) for x in losses],
        "phases": phases, "setup_s": setup_s,
        "compile_s": compile_s, "first_step_s": step_s[0],
        "ms_per_step_median": ms, "tokens_per_s": tokens_s,
        "ms_per_sync_step": (1e3 * statistics.median(syncs)
                             if syncs else None),
        "step_ms": [1e3 * t for t in step_s],
        "tpu_custom_calls": kernels,
    }
    log(f"{label}: loss {losses[0]:.4f} -> {losses[-1]:.4f}, "
        f"{ms:.1f} ms/step ({tokens_s:.0f} tokens/s), "
        f"sync step {result['ms_per_sync_step']} ms, "
        f"compile {compile_s} s, first step {step_s[0]:.1f} s, "
        f"Mosaic kernels {kernels}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-group exchange on a 4,1,1 mesh")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: FAIL: no checkout around {ROOT} (src/repro)",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.configs import get_config
    from repro.kernels import backend as kbackend
    from repro.launch.train import pallas_kernels_compiled, use_compile_cache

    dev = jax.devices()[0]
    want = 4 if args.four_chips else 1
    try:
        check(dev.platform == "tpu",
              f"JAX found no TPU (platform {dev.platform!r})")
        check(jax.device_count() >= want,
              f"{want} chip(s) needed, {jax.device_count()} found")
        check(kbackend.resolve_backend().name == "tpu-mosaic",
              f"kernel backend is {kbackend.resolve_backend().name}")
        use_pallas = pallas_kernels_compiled()
        check(use_pallas, "the launcher would not turn use_pallas on")
        cache_dir = use_compile_cache()
        log(f"device {dev.platform} {dev.device_kind} x{jax.device_count()}"
            f", jax {jax.__version__}, compile cache {cache_dir}")

        mc = get_config("gpt2-small")
        results = {}
        t_all = time.perf_counter()
        if args.four_chips:
            for comp in ("none", "int8-wire"):
                results[comp] = train(f"4x1x1 pier {comp}", mc,
                                      mesh_shape=(4, 1, 1),
                                      compression=comp)
            results["adamw"] = train("4x1x1 adamw", mc, mesh_shape=(4, 1, 1),
                                     optimizer="adamw")
        else:
            results["kernels"] = kernel_parity()
            for comp in ("none", "int8-wire"):
                results[comp] = train(f"1x1x1 pier {comp}", mc,
                                      mesh_shape=(1, 1, 1),
                                      compression=comp)
        stats = dev.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        log(f"peak device memory: {peak} bytes "
            f"(limit {stats.get('bytes_limit')})")
        log(f"total {time.perf_counter() - t_all:.1f} s")
        out_dir = ROOT / "chiprun_out"
        out_dir.mkdir(exist_ok=True)
        name = "chip_smoke_four.json" if args.four_chips else "chip_smoke.json"
        (out_dir / name).write_text(json.dumps(
            {"device_kind": dev.device_kind, "count": jax.device_count(),
             "peak_bytes_in_use": peak, "results": results}, indent=1))
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
