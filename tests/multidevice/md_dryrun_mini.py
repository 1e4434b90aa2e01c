"""Mini dry-run: the dryrun machinery end-to-end with a reduced arch on a
(2,2,2) pier mesh (pod-less) and a multi-pod analogue.

NOTE: importing repro.launch.dryrun appends a 512 host-device count to
XLA_FLAGS before jax initializes (by design — its first lines); the small
meshes here use the first 8 of them.
"""

from repro.launch.dryrun import (  # noqa: E402  (must be first: sets XLA_FLAGS)
    _compile_record, collective_bytes, lower_serve, lower_train,
    make_train_batch_specs)

import jax

from repro.config import ParallelConfig, TrainConfig, InputShape
from repro.configs import get_reduced_config
from repro.launch.mesh import small_mesh

assert jax.device_count() == 512, jax.device_count()

# meshes must span ALL devices: XLA's SPMD partitioner CHECK-fails on
# gather/scatter ops when the mesh covers a strict subset of the world
# (same limitation documented in parallel/sharding.py).
shape = InputShape("mini_train", 64, 64, "train")
mc = get_reduced_config("deepseek-v2-236b")
pc = ParallelConfig(data_axis_size=64, model_axis_size=8, data_outer=2,
                    scan_layers=True, remat="full", num_microbatches=2)
tc = TrainConfig(global_batch_size=64, seq_len=64)
mesh = small_mesh((2, 32, 8), ("data_outer", "data_inner", "model"))

out = lower_train(mc, tc, pc, mesh, shape, steps=("inner", "warmup", "outer"))
rec = {k: _compile_record(v) for k, v in out.items()}
for k, r in rec.items():
    assert r["flops"] > 0 or k == "outer", (k, r["flops"])

# inner has no big cross-group collective, warmup/outer do (checked by bytes:
# warmup adds a gradient-sized all-reduce; inner only scalar metrics)
inner_ar = rec["inner"]["collective_bytes"].get("all-reduce", 0)
warm_ar = rec["warmup"]["collective_bytes"].get("all-reduce", 0)
outer_ar = rec["outer"]["collective_bytes"].get("all-reduce", 0)
assert warm_ar > inner_ar, (warm_ar, inner_ar)
assert outer_ar > 0

# multi-pod analogue mesh: (pod=2, data_outer=1, data_inner=32, model=8)
mesh_mp = small_mesh((2, 1, 32, 8),
                     ("pod", "data_outer", "data_inner", "model"))
pc_mp = ParallelConfig(data_axis_size=32, model_axis_size=8, num_pods=2,
                       data_outer=1, scan_layers=True, remat="full",
                       num_microbatches=2)
out_mp = lower_train(mc, tc, pc_mp, mesh_mp, shape, steps=("inner",))
assert out_mp["inner"] is not None

# serve paths
dshape = InputShape("mini_decode", 64, 64, "decode")
sv = lower_serve(mc, pc, mesh, dshape, prefill=False)
assert _compile_record(sv["decode"])["flops"] >= 0
pshape = InputShape("mini_prefill", 64, 64, "prefill")
pv = lower_serve(mc, pc, mesh, pshape, prefill=True)
assert pv["prefill"] is not None

print("MD_DRYRUN_MINI_OK")
