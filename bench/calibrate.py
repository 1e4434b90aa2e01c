"""Readings that the limits of a cell's correctness check are set from.

``python bench/calibrate.py --workload <cell> --seeds 1 2 ... [--control
3] [--faults half_batch ...]`` drives the program as a run's set-up does,
through ``train_step`` for the cell's ``check_steps`` (no window), and
compares it with the plain reference on every seed: the lower readings.
The control (the reference at fp8, ``bench/reference.py``) is compared
with the reference on the first ``--control`` seeds, and each planted fault
(``bench/faults.py``) on the first three seeds: the upper readings. One
JSON line per reading; ``bench/workloads/<cell>.json`` keeps the limits set
from them.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", nargs="*", default=[])
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH.parent / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(BENCH.parent / "src"))
    import jax

    import check as C
    import faults as F
    import run as R

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cell = R.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"calibrate: FAIL: needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 2
    used = devices[:cell.chips]
    K = int(cell.check["check_steps"])

    def program(seed, fault=None):
        with (F.plant(fault) if fault else contextlib.nullcontext()):
            prog = R.Program(cell, seed, used)
            try:
                return prog.warm_up(K, steps=K)
            finally:
                prog.close()
                del prog
                gc.collect()

    def emit(kind, seed, gaps):
        print(json.dumps({"kind": kind, "seed": seed, **gaps}), flush=True)

    refs = {}
    for i, seed in enumerate(args.seeds):
        got = program(seed)
        refs[seed] = C.reference_readings(cell, seed)
        emit("program", seed, C.readings_gaps(got, refs[seed]))
        if i < args.control:
            ctl = C.reference_readings(cell, seed, precision="fp8")
            emit("control", seed, C.readings_gaps(ctl, refs[seed]))
    for fault in args.faults:
        for seed in args.seeds[:3]:
            emit(fault, seed, C.readings_gaps(program(seed, fault),
                                              refs[seed]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
