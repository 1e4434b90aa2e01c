import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# Must precede all other imports (jax locks the platform and the device
# count on first init): a CPU compile tool, as launch/dryrun.py is.

"""§Perf hillclimb driver: lower/compile named VARIANTS of a (arch × shape)
pair and report the roofline-term deltas vs the paper-faithful baseline.

    python -m repro.launch.perf --pair granite-8b:train_4k --variant baseline
    python -m repro.launch.perf --pair granite-8b:train_4k --list

Each variant is {parallel-config overrides, model overrides, serve-sharding
overrides}; records land in experiments/perf/<pair>__<variant>.json.
"""

import argparse
import json
import time
from typing import Dict


from repro.config import INPUT_SHAPES, ParallelConfig, TrainConfig
from repro.launch import mesh as M
from repro.launch.dryrun import (
    _compile_record, _mesh_for, auto_microbatches, cost_depths,
    extrapolate_cost, lower_serve, lower_train, resolve_model)
from repro.models.attention import chunk_policy

# ---------------------------------------------------------------------------
# variant definitions (the §Perf candidate changes)
# ---------------------------------------------------------------------------

VARIANTS: Dict[str, Dict[str, Dict]] = {
    # paper-faithful baseline: fp32 master+moments, remat=full, FSDP-in-group
    "baseline": {},
    # activation-checkpoint policy: no remat (more memory, -25% flops)
    "no_remat": {"pc": {"remat": "none"}},
    # selective remat: save matmul outputs, recompute the cheap chains
    "selective_remat": {"pc": {"remat": "selective"}},
    "selective_opt_bf16": {"pc": {"remat": "selective"},
                           "tc": {"opt_state_dtype": "bfloat16"}},
    # fewer microbatches: fewer FSDP re-gathers per step (all-gather /nm)
    "nm2": {"pc_nm": 2},
    "nm1": {"pc_nm": 1},
    # beyond-paper: bf16 optimizer state (halves AdamW m/v bytes)
    "opt_bf16": {"tc": {"opt_state_dtype": "bfloat16"}},
    # bf16 master params (paper's 'BF16 model' reading): 4->2 bytes/param
    "master_bf16": {"mc": {"param_dtype": "bfloat16"}},
    # both memory levers together
    "bf16_all": {"tc": {"opt_state_dtype": "bfloat16"},
                 "mc": {"param_dtype": "bfloat16"}},
    # group structure: 2 groups instead of 4 (more in-group sharding)
    "groups2": {"data_outer": 2},
    "groups8": {"data_outer": 8},
    # inference: expert-parallel over BOTH data_inner and model axes
    # (kills the per-layer FSDP all-gather of expert stacks)
    "ep2d": {"ep2d": True},
    # inference: no FSDP (pure TP serving; weights replicated over data)
    "serve_no_fsdp": {"pc": {"fsdp": False}},
    # sliding-window length ablation for long-context decode
    "swa_1k": {"mc": {"sliding_window": 1024}},
    # the memory-fit combo for the 100B+ MoEs: 2 groups + bf16 everywhere
    "fit_combo": {"data_outer": 2,
                  "tc": {"opt_state_dtype": "bfloat16"},
                  "mc": {"param_dtype": "bfloat16"}},
    # fit_combo + relaxed remat (is there flops headroom once memory fits?)
    "fit_combo_norematt": {"data_outer": 2,
                           "tc": {"opt_state_dtype": "bfloat16"},
                           "mc": {"param_dtype": "bfloat16"},
                           "pc": {"remat": "none"}},
}


def run_variant(arch: str, shape_name: str, variant: str,
                data_outer: int = 4) -> Dict:
    shape = INPUT_SHAPES[shape_name]
    spec = VARIANTS[variant]
    mc = resolve_model(arch, shape)
    assert mc is not None, "pair is skipped for this shape"
    if "mc" in spec:
        mc = mc.replace(**spec["mc"])
    mesh = _mesh_for("single", spec.get("data_outer", data_outer))
    sizes = M.axis_sizes(mesh)
    pc = ParallelConfig(
        data_axis_size=sizes["data_outer"] * sizes["data_inner"],
        model_axis_size=sizes["model"],
        data_outer=sizes["data_outer"],
        scan_layers=True,
        remat="full" if shape.kind == "train" else "none")
    pc = pc.replace(num_microbatches=auto_microbatches(shape, pc))
    if "pc" in spec:
        pc = pc.replace(**spec["pc"])
    if "pc_nm" in spec:
        pc = pc.replace(num_microbatches=spec["pc_nm"])
    tc = TrainConfig(global_batch_size=shape.global_batch,
                     seq_len=shape.seq_len,
                     **spec.get("tc", {}))

    if spec.get("ep2d"):
        # widen the expert-parallel axis to (data_inner, model)
        import repro.parallel.sharding as S
        orig = S._physical

        def patched(logical, *, fsdp, experts):
            if logical == S.EXP and experts:
                return ("data_inner", "model")
            return orig(logical, fsdp=fsdp, experts=experts)

        S._physical = patched
        import repro.parallel.axes as AX
        orig_rules = AX.pier_rules

        def patched_rules(**kw):
            r = orig_rules(**kw)
            rules = dict(r.rules)
            if rules.get("experts"):
                rules["experts"] = ("data_inner", "model")
            return AX.LogicalAxisRules(rules=rules,
                                       axis_sizes=r.axis_sizes)

        AX.pier_rules = patched_rules

    rec = {"arch": arch, "shape": shape_name, "variant": variant,
           "time": time.time(),
           "pc": {"num_microbatches": pc.num_microbatches,
                  "remat": pc.remat, "fsdp": pc.fsdp,
                  "data_outer": sizes["data_outer"]},
           "tc": {"opt_state_dtype": tc.opt_state_dtype},
           "mc": {"param_dtype": mc.param_dtype,
                  "sliding_window": mc.sliding_window}}

    t0 = time.time()
    if shape.kind == "train":
        out = lower_train(mc, tc, pc, mesh, shape,
                          steps=("inner", "outer"))
        rec["fit"] = {k: _compile_record(v) for k, v in out.items()}
    elif shape.kind == "prefill":
        out = lower_serve(mc, pc, mesh, shape, prefill=True)
        rec["fit"] = {"prefill": _compile_record(out["prefill"])}
    else:
        out = lower_serve(mc, pc, mesh, shape, prefill=False)
        rec["fit"] = {"decode": _compile_record(out["decode"])}
    del out
    rec["compile_seconds"] = time.time() - t0

    # cost extrapolation (exact flops/collectives), same method as dryrun
    L1, L2, C = cost_depths(mc)
    nm_cost = 2 if (mc.is_moe and shape.kind == "train") else 1
    cost = {}
    with chunk_policy("never"):
        for L in (L1, L2):
            mcl = mc.replace(num_layers=L, mlstm_chunk=0)
            pcl = pc.replace(scan_layers=False, num_microbatches=nm_cost,
                             remat="none")
            if shape.kind == "train":
                cl = lower_train(mcl, tc, pcl, mesh, shape, steps=("inner",))
                cost[L] = _compile_record(cl["inner"])
                if nm_cost > 1:
                    r = cost[L]
                    r["flops"] *= nm_cost
                    r["bytes_accessed"] *= nm_cost
                    r["collective_bytes"] = {
                        k: v * nm_cost if k in ("all-gather", "all-to-all")
                        else v for k, v in r["collective_bytes"].items()}
            elif shape.kind == "prefill":
                cl = lower_serve(mcl, pcl, mesh, shape, prefill=True)
                cost[L] = _compile_record(cl["prefill"])
            else:
                cl = lower_serve(mcl, pcl, mesh, shape, prefill=False)
                cost[L] = _compile_record(cl["decode"])
            del cl
    rec["extrapolated"] = extrapolate_cost(
        cost[L1], cost[L2], L1, L2, mc.num_layers)
    return rec


def summarize(rec: Dict) -> str:
    key = next(iter(rec["fit"]))
    f = rec["fit"][key]
    mem = (f["argument_bytes_per_device"] + f["temp_bytes_per_device"]
           + f["output_bytes_per_device"]) / 2**30
    corr = mem - f.get("cpu_convert_artifact_bytes", 0) / 2**30
    e = rec["extrapolated"]
    coll = sum(e["collective_bytes"].values())
    return (f"{rec['variant']:14s} mem={mem:7.1f}GiB (corr {corr:7.1f}) "
            f"flops/dev={e['flops']:.3g} hbm={e['bytes_accessed']:.3g} "
            f"coll={coll/2**30:.1f}GiB")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--pair", required=False, default="")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default="experiments/perf")
    args = ap.parse_args(argv)
    if args.list:
        print("\n".join(VARIANTS))
        return
    arch, shape = args.pair.split(":")
    rec = run_variant(arch, shape, args.variant)
    os.makedirs(args.out, exist_ok=True)
    tag = f"{arch}__{shape}__{args.variant}"
    with open(os.path.join(args.out, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=2)
    print(summarize(rec))


if __name__ == "__main__":
    main()
