import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# The lines above MUST run before any other import (jax locks the platform
# and the device count at first init). A compile tool: it runs on the CPU,
# never on a chip, and so do the children of --all. 512 host devices back
# the 2x16x16 production mesh; the appended count overrides an earlier one
# and every other flag of the caller stays.

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes and extract the roofline terms.

Per combo this produces:

- **fit compile** (full depth, scan_layers + remat + microbatching): proves
  the sharding is coherent on the single-pod (16,16) AND multi-pod (2,16,16)
  meshes; ``memory_analysis()`` gives honest bytes/device.
- **cost compiles** (unrolled, depth L1 = prefix+C and L2 = prefix+2C):
  XLA's ``cost_analysis()`` undercounts ``lax.scan`` bodies (counted once),
  so FLOPs / HBM bytes / per-collective bytes are measured exactly at two
  small depths and extrapolated linearly in depth — exact for layer-stacked
  models (every layer past the prefix contributes identical HLO).
- **train shapes additionally** lower ``outer_step`` (the 1/H global sync)
  and ``warmup_step`` (per-step global AdamW baseline) so the roofline can
  price Pier against the paper's baseline.

Usage:
  python -m repro.launch.dryrun --arch granite-8b --shape train_4k
  python -m repro.launch.dryrun --all          # orchestrates subprocesses
"""

import argparse
import json
import re
import subprocess
import sys
import time
from collections import defaultdict
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import (
    InputShape, INPUT_SHAPES, ModelConfig, ParallelConfig, TrainConfig)
from repro.configs import assigned_architectures, get_config
from repro.launch import mesh as M
from repro.models import registry as R
from repro.models import transformer as T
from repro.parallel.steps import build_serve_steps, build_train_steps

DEFAULT_OUT = "experiments/dryrun"

# Architectures where long_500k is skipped (full-context attention without a
# sliding-window variant) — see DESIGN.md §Arch-applicability.
LONG_SKIP = {
    "deepseek-v2-236b": "MLA latent attention is full-context; MLA+SWA is "
                        "not a published configuration",
    "kimi-k2-1t-a32b": "full-context GQA MoE; no sub-quadratic variant in "
                       "the model family",
    "whisper-large-v3": "encoder-decoder; 500k-token decoder context is not "
                        "meaningful for the architecture",
}
# Dense archs that run long_500k via the sliding-window variant:
SWA_WINDOW = 4096


def resolve_model(arch: str, shape: InputShape) -> Optional[ModelConfig]:
    mc = get_config(arch)
    if shape.name == "long_500k":
        if arch in LONG_SKIP:
            return None
        if not mc.sub_quadratic:
            mc = mc.replace(sliding_window=SWA_WINDOW,
                            name=mc.name + "+swa4096")
    return mc


def auto_microbatches(shape: InputShape, pc: ParallelConfig) -> int:
    if shape.kind != "train":
        return 1
    per_group = shape.global_batch // pc.num_groups
    return max(1, per_group // 8)


def _specs_of(tree, shardings):
    return jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        tree, shardings)


def make_train_batch_specs(mc, shape, bundle):
    batch = {
        "tokens": jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len),
                                       jnp.int32),
        "labels": jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len),
                                       jnp.int32),
    }
    if mc.is_encoder_decoder:
        batch["frames"] = jax.ShapeDtypeStruct(
            (shape.global_batch, mc.encoder_seq_len, mc.d_model), jnp.float32)
    shardings = bundle.batch_sharding(batch)
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        batch, shardings)


# ---------------------------------------------------------------------------
# HLO collective accounting
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_CONVERT_RE = re.compile(
    r"wrapped_convert(?:_computation)?[^\(]*\(param_0[^:]*: "
    r"(?:bf16|f16)\[([\d,]*)\]\) -> f32\[([\d,]*)\]")


def cpu_convert_artifact_bytes(hlo_text: str) -> int:
    """Bytes of whole-tensor bf16->f32 converts hoisted out of loops.

    XLA:CPU legalizes bf16 dots by upcasting operands to f32; the per-layer
    converts are then hoisted out of the ``lax.scan`` while-loop as
    loop-invariant whole-stack f32 copies that stay live for the entire
    loop. A TPU backend consumes bf16 on the MXU directly, so these buffers
    do not exist on the target hardware. We quantify them so the memory
    report can show measured and corrected bytes side by side.
    """
    total = 0
    for m in _CONVERT_RE.finditer(hlo_text):
        dims = m.group(2)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * 4
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind OUTPUT bytes (per device) summed over the module.

    ``-start``/``-done`` pairs are counted once (the start op carries the
    shape; done lines reference the same buffer).
    """
    out: Dict[str, int] = defaultdict(int)
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        out[m.group(2)] += _shape_bytes(m.group(1))
    return dict(out)


# ---------------------------------------------------------------------------
# one combo
# ---------------------------------------------------------------------------


def _mesh_for(mesh_kind: str, data_outer: int):
    return M.make_pier_mesh(multi_pod=(mesh_kind == "multi"),
                            data_outer=data_outer)


def _compile_record(compiled) -> Dict:
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    return {
        "argument_bytes_per_device": int(ma.argument_size_in_bytes),
        "output_bytes_per_device": int(ma.output_size_in_bytes),
        "temp_bytes_per_device": int(ma.temp_size_in_bytes),
        "alias_bytes_per_device": int(ma.alias_size_in_bytes),
        "cpu_convert_artifact_bytes": cpu_convert_artifact_bytes(txt),
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "collective_bytes": collective_bytes(txt),
    }


def lower_train(mc, tc, pc, mesh, shape, *, steps=("inner",)):
    bundle = build_train_steps(mc, tc, pc, mesh)
    state_shapes = jax.eval_shape(bundle.init_state, jax.random.PRNGKey(0))
    state_specs = _specs_of(state_shapes, bundle.state_shardings)
    batch_specs = make_train_batch_specs(mc, shape, bundle)
    step_spec = jax.ShapeDtypeStruct((), jnp.int32)
    out = {}
    if "inner" in steps:
        out["inner"] = bundle.inner_step.lower(
            state_specs, batch_specs, step_spec).compile()
    if "warmup" in steps:
        out["warmup"] = bundle.warmup_step.lower(
            state_specs, batch_specs, step_spec).compile()
    if "outer" in steps:
        outer_shapes = jax.eval_shape(bundle.init_outer, state_shapes)
        outer_specs = _specs_of(outer_shapes, bundle.outer_shardings)
        mu = jax.ShapeDtypeStruct((), jnp.float32)
        out["outer"] = bundle.outer_step.lower(
            state_specs, outer_specs, mu, mu).compile()
    return out


def lower_serve(mc, pc, mesh, shape, *, prefill: bool):
    batch = shape.global_batch
    bundle = build_serve_steps(mc, pc, mesh, batch=batch,
                               max_len=shape.seq_len)
    pshapes = jax.eval_shape(
        lambda k: R.init_params(k, mc, scan_layers=pc.scan_layers),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    # Serving uses the bf16 model copy (paper: BF16 model / FP32 optimizer;
    # the fp32 master lives with the trainer, not the server).
    pshapes = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(
            l.shape,
            jnp.dtype(mc.dtype) if l.dtype == jnp.float32 else l.dtype),
        pshapes)
    param_specs = _specs_of(pshapes, bundle.param_shardings)
    if prefill:
        b = {"tokens": jax.ShapeDtypeStruct((batch, shape.seq_len), jnp.int32)}
        if mc.is_encoder_decoder:
            b["frames"] = jax.ShapeDtypeStruct(
                (batch, mc.encoder_seq_len, mc.d_model), jnp.float32)
        return {"prefill": bundle.prefill_step.lower(param_specs, b).compile()}
    state_shapes = jax.eval_shape(
        lambda: R.init_decode_state(mc, batch, shape.seq_len,
                                    scan_layers=pc.scan_layers))
    state_specs = _specs_of(state_shapes, bundle.state_shardings)
    toks = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    return {"decode": bundle.serve_step.lower(
        param_specs, state_specs, toks).compile()}


def cost_depths(mc: ModelConfig) -> Tuple[int, int, int]:
    """(L1, L2, C) unrolled depths for the linear-in-depth extrapolation."""
    prefix, C, n, suffix = T.layer_segments(mc)
    return prefix + C, prefix + 2 * C, C


def run_combo(arch: str, shape_name: str, mesh_kind: str, data_outer: int,
              *, do_cost: bool = True, outer_sharded: bool = False) -> Dict:
    shape = INPUT_SHAPES[shape_name]
    mc = resolve_model(arch, shape)
    record: Dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "data_outer": data_outer, "time": time.time(),
    }
    if mc is None:
        record["skipped"] = LONG_SKIP[arch]
        return record
    mesh = _mesh_for(mesh_kind, data_outer)
    sizes = M.axis_sizes(mesh)
    pc = ParallelConfig(
        data_axis_size=sizes.get("data_outer", 1) * sizes.get("data_inner", 1),
        model_axis_size=sizes["model"],
        num_pods=sizes.get("pod", 1),
        data_outer=sizes.get("data_outer", 1),
        scan_layers=True,
        remat="full" if shape.kind == "train" else "none",
    )
    pc = pc.replace(num_microbatches=auto_microbatches(shape, pc))
    tc = TrainConfig(global_batch_size=shape.global_batch,
                     seq_len=shape.seq_len)
    if outer_sharded:
        # sharded quantized outer exchange (DESIGN.md §10): each device
        # compresses/exchanges only its Δθ shard over data_inner×model
        from repro.config import OuterCommConfig
        tc = tc.replace(outer_comm=OuterCommConfig(
            compression="quantize", sharded=True))
        record["outer_sharded"] = True
    record["config"] = {
        "num_groups": pc.num_groups, "num_microbatches": pc.num_microbatches,
        "params": R.count_params(mc), "active_params": R.count_params(mc, True),
        "model_name": mc.name,
    }

    # ---- fit compile (full depth) ----
    t0 = time.time()
    if shape.kind == "train":
        steps = ("inner", "warmup", "outer") if mesh_kind == "single" \
            else ("inner",)
        compiled = lower_train(mc, tc, pc, mesh, shape, steps=steps)
    elif shape.kind == "prefill":
        compiled = lower_serve(mc, pc, mesh, shape, prefill=True)
    else:
        compiled = lower_serve(mc, pc, mesh, shape, prefill=False)
    record["fit"] = {k: _compile_record(v) for k, v in compiled.items()}
    record["fit_compile_seconds"] = time.time() - t0
    del compiled

    # ---- cost compiles (small unrolled depths, single-pod only) ----
    # chunk_policy("never") + mlstm_chunk=0 force the loop-free quadratic
    # forms so cost_analysis() counts every FLOP exactly (lax.scan bodies
    # are otherwise counted once); memory honesty comes from the fit
    # compile above, which uses the production (chunked/scanned) paths.
    if do_cost and mesh_kind == "single":
        from repro.models.attention import chunk_policy

        L1, L2, C = cost_depths(mc)
        cost = {}
        # MoE train grads at nm=1 trip the same XLA partitioner CHECK (the
        # microbatch scan sidesteps it); use nm=2 and scale the in-loop
        # terms back up. The scan body holds exactly 1/nm of the step's
        # model work, so flops/bytes scale by nm; grad all-reduce /
        # reduce-scatter run once per step (outside the loop) either way.
        nm_cost = 2 if (mc.is_moe and shape.kind == "train") else 1
        with chunk_policy("never"):
            for L in (L1, L2):
                mcl = mc.replace(num_layers=L, mlstm_chunk=0)
                pcl = pc.replace(scan_layers=False, num_microbatches=nm_cost,
                                 remat="none")
                if shape.kind == "train":
                    cl = lower_train(mcl, tc, pcl, mesh, shape,
                                     steps=("inner",))
                    cost[L] = _compile_record(cl["inner"])
                    if nm_cost > 1:
                        r = cost[L]
                        r["flops"] *= nm_cost
                        r["bytes_accessed"] *= nm_cost
                        r["collective_bytes"] = {
                            k: v * nm_cost if k in ("all-gather", "all-to-all")
                            else v
                            for k, v in r["collective_bytes"].items()}
                        r["cost_nm_scaled"] = nm_cost
                elif shape.kind == "prefill":
                    cl = lower_serve(mcl, pcl, mesh, shape, prefill=True)
                    cost[L] = _compile_record(cl["prefill"])
                else:
                    cl = lower_serve(mcl, pcl, mesh, shape, prefill=False)
                    cost[L] = _compile_record(cl["decode"])
                del cl
        record["cost_depths"] = {"L1": L1, "L2": L2, "cycle": C,
                                 "full_depth": mc.num_layers}
        record["cost"] = {str(k): v for k, v in cost.items()}
        record["extrapolated"] = extrapolate_cost(
            cost[L1], cost[L2], L1, L2, mc.num_layers)
    return record


def extrapolate_cost(r1: Dict, r2: Dict, L1: int, L2: int, L: int) -> Dict:
    """Linear-in-depth extrapolation of flops / bytes / collectives."""
    def lin(a, b):
        per_layer = (b - a) / (L2 - L1)
        return a + per_layer * (L - L1)

    out = {
        "flops": lin(r1["flops"], r2["flops"]),
        "bytes_accessed": lin(r1["bytes_accessed"], r2["bytes_accessed"]),
    }
    kinds = set(r1["collective_bytes"]) | set(r2["collective_bytes"])
    out["collective_bytes"] = {
        k: max(0.0, lin(r1["collective_bytes"].get(k, 0),
                        r2["collective_bytes"].get(k, 0)))
        for k in kinds
    }
    return out


# ---------------------------------------------------------------------------
# orchestration
# ---------------------------------------------------------------------------


def all_combos():
    for arch in assigned_architectures():
        for shape in INPUT_SHAPES:
            yield arch, shape


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="")
    ap.add_argument("--shape", default="", choices=[""] + list(INPUT_SHAPES))
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--data-outer", type=int, default=4)
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--all", action="store_true",
                    help="run every combo in subprocesses")
    ap.add_argument("--no-cost", action="store_true")
    ap.add_argument("--outer-sharded", action="store_true",
                    help="lower the train steps with the sharded quantized "
                         "outer exchange (DESIGN.md §10)")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)

    if args.all:
        failures = []
        for arch, shape in all_combos():
            for mesh_kind in (["single", "multi"] if args.mesh == "both"
                              else [args.mesh]):
                tag = f"{arch}__{shape}__{mesh_kind}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"[skip] {tag} (exists)", flush=True)
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape,
                       "--mesh", mesh_kind, "--out", args.out,
                       "--data-outer", str(args.data_outer)]
                if args.no_cost:
                    cmd.append("--no-cost")
                t0 = time.time()
                r = subprocess.run(cmd, capture_output=True, text=True)
                ok = r.returncode == 0
                print(f"[{'ok' if ok else 'FAIL'}] {tag} "
                      f"({time.time()-t0:.0f}s)", flush=True)
                if not ok:
                    failures.append(tag)
                    with open(os.path.join(args.out, tag + ".err"), "w") as f:
                        f.write(r.stdout[-5000:] + "\n--- stderr ---\n"
                                + r.stderr[-10000:])
        print(f"done; {len(failures)} failures: {failures}")
        sys.exit(1 if failures else 0)

    assert args.arch and args.shape, "--arch and --shape required"
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for mesh_kind in meshes:
        record = run_combo(args.arch, args.shape, mesh_kind, args.data_outer,
                           do_cost=not args.no_cost,
                           outer_sharded=args.outer_sharded)
        tag = f"{args.arch}__{args.shape}__{mesh_kind}"
        path = os.path.join(args.out, tag + ".json")
        with open(path, "w") as f:
            json.dump(record, f, indent=2)
        if "skipped" in record:
            print(f"{tag}: SKIPPED ({record['skipped']})")
        else:
            fit = record["fit"]
            key = next(iter(fit))
            mem = (fit[key]["argument_bytes_per_device"]
                   + fit[key]["temp_bytes_per_device"]) / 2**30
            print(f"{tag}: ok mem/dev={mem:.1f}GiB "
                  f"compile={record['fit_compile_seconds']:.0f}s")


if __name__ == "__main__":
    main()
