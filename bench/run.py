"""One run of one benchmark cell: ``python bench/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>``.

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a model
configuration (``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``); the cell's own file
(``bench/workloads/<cell>.json``) gives the steps the correctness check
follows and the limits of its numbers. Every per-layer metric is read by
``bench/metrics/<metric>.py``. Nothing here names a cell, a configuration
or a metric: a new one is a new file and a new ``BENCHMARK.json`` entry.

A run drives the program's own training path, ``Trainer.train_step`` on a
``Mesh``, fed by its ``DataPipeline`` with host-side batches from
``bench/traffic.py``:

1. set-up: imports and the chip, the trainer's state made on the device
   from the seed, and warm-up through ``train_step`` until the outer step
   has run twice (and the check's steps are done); every program then
   comes from JAX's compilation cache in ``.jax_cache/``;
2. the window: ``train_step`` back to back for ``--seconds``, ended at the
   first step return past it and a ``block_until_ready`` on the state;
   compilations and traces of a function inside it are counted, and must
   be none;
3. with ``--trace 1`` the window runs under the profiler and ends at the
   first outer sync after ``TRACE_MIN_S`` seconds (or at ``--seconds``),
   and the per-layer metrics are read from its trace
   (``bench/devtrace.py``);
4. once the window has closed and the program's state is freed, the plain
   reference (``bench/reference.py``) follows the check's first steps from
   the seed, and ``bench/check.py`` compares.

Earlier lines on stdout report the device, the set-up's parts, the window's
counts and the peak memory; the last line is one JSON object. Without a TPU,
or with fewer chips than the cell asks, the run exits non-zero and prints
no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"
# A traced window ends at the first outer sync after this many seconds (or
# at --seconds): whole sync periods, and a trace the run can read in time.
TRACE_MIN_S = 5.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


class RunError(Exception):
    """The run cannot produce a result (no chip, a bad cell)."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict      # bench/configs/<config>.json
    traffic: dict     # bench/traffic/<traffic>.json
    check: dict       # bench/workloads/<cell>.json
    end_to_end: list = field(default_factory=list)   # metric entries
    per_layer: list = field(default_factory=list)    # metric entries


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise RunError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_cell(name: str) -> Cell:
    """The cell ``name`` of BENCHMARK.json with its files."""
    bench = _read_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise RunError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    applies = lambda m: name in m.get("workloads", [name])  # noqa: E731
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_read_json(ROOT / configs[w["config"]]["file"]),
        traffic=_read_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        check=_read_json(BENCH / "workloads" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)])


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------


def program_configs(cell: Cell):
    """(ModelConfig, TrainConfig, ParallelConfig, mesh shape) as the files
    state them; every training setting is passed explicitly."""
    import dataclasses

    from repro.config import (ModelConfig, OuterCommConfig, ParallelConfig,
                              TrainConfig)
    from repro.configs import get_config
    from repro.launch.train import pallas_kernels_compiled

    cfg, tr = cell.config, cell.traffic
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    mc = get_config(cfg["program_config"]).replace(**{
        k: v for k, v in cfg.items()
        if k in fields and k not in ("name", "source")})
    train = dict(tr["train"])
    train["momentum_decay"] = tuple(tuple(x) for x in train["momentum_decay"])
    train["outer_comm"] = OuterCommConfig(**train.get("outer_comm", {}))
    tc = TrainConfig(seq_len=tr["seq_len"],
                     global_batch_size=tr["global_batch"], **train)
    shape = tuple(tr["mesh"])
    pc = ParallelConfig(data_axis_size=shape[0] * shape[1],
                        model_axis_size=shape[2], data_outer=shape[0],
                        use_pallas=pallas_kernels_compiled())
    return mc, tc, pc, shape


def make_traffic(cell: Cell, seed: int):
    sys.path.insert(0, str(BENCH))
    from traffic import MarkovTraffic

    tr = cell.traffic
    return MarkovTraffic(vocab=cell.config["vocab_size"],
                         seq_len=tr["seq_len"],
                         global_batch=tr["global_batch"],
                         groups=tr["mesh"][0], seed=seed,
                         **tr.get("chain", {}))


def _norm_fns():
    """Jitted per-leaf, per-group norms of the program's (G,)-stacked
    trees: of a tree, and of its difference from an unstacked base."""
    import jax
    import jax.numpy as jnp

    def norms(tree):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                                           axis=tuple(range(1, x.ndim))))
                          for x in jax.tree.leaves(tree)])

    def diff(tree, base):
        return norms(jax.tree.map(
            lambda x, b: x.astype(jnp.float32) - b.astype(jnp.float32)[None],
            tree, base))

    return jax.jit(norms), jax.jit(diff)


class Program:
    """The trainer on its mesh, its input pipeline, and the check's
    readings taken while set-up drives it through its first steps."""

    def __init__(self, cell: Cell, seed: int, devices, traffic=None):
        import jax
        import numpy as np

        from repro.data.pipeline import DataPipeline
        from repro.launch import mesh as M
        from repro.launch.train import Trainer

        mc, tc, pc, shape = program_configs(cell)
        self.tc = tc.replace(seed=seed)
        self.mesh = jax.sharding.Mesh(
            np.array(devices[:math.prod(shape)]).reshape(shape),
            ("data_outer", "data_inner", "model"))
        self.trainer = Trainer(mc, self.tc, pc, self.mesh)
        jax.block_until_ready((self.trainer.state, self.trainer.outer))
        self.traffic = traffic or make_traffic(cell, seed)
        self.pipeline = DataPipeline(self.mesh, M.data_axes(self.mesh),
                                     self.traffic.batch)

    def warm_up(self, check_steps: int, steps: int | None = None) -> dict:
        """Run ``train_step`` for ``steps`` steps (by default through two
        outer syncs, and the check's ``check_steps``: the second sync is the
        first whose outer state the outer step itself made, and traces the
        outer step once more); returns the check's readings:
        each step's loss, the first gradient per leaf and group (from AdamW's
        first moment after one step), and the change of the parameters over
        ``check_steps`` steps."""
        import jax

        t = self.trainer
        norms, diff = _norm_fns()
        H = self.tc.sync_interval
        base = t.outer.anchor  # the initial weights until the first sync
        if check_steps >= H:
            base = jax.tree.map(lambda x: x.copy(), base)
        out = {"loss": []}
        for s in range(max(2 * H, check_steps) if steps is None else steps):
            m = t.train_step(next(self.pipeline))
            if s < check_steps:
                out["loss"].append(m["loss"])
            if s == 0:
                out["grad_norms"] = jax.device_get(
                    norms(t.state.opt.mu)).T / (1.0 - self.tc.adam_beta1)
            if s == check_steps - 1:
                out["change_norms"] = jax.device_get(
                    diff(t.state.params, base)).T
        del base
        return out

    def close(self):
        self.pipeline.close()
        self.trainer = None


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def _percentile(xs, q):
    import numpy as np
    return float(np.percentile(np.asarray(xs), q))


def _peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        require_chip: bool = True) -> dict:
    """One run; returns the result object (the last line's JSON)."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_chip:
        if dev.platform != "tpu":
            raise RunError(f"JAX found no TPU (platform {dev.platform!r})")
        if len(devices) < cell.chips:
            raise RunError(f"the cell needs {cell.chips} chips, "
                           f"{len(devices)} found")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = [0, 0]  # programs compiled or loaded, functions traced

    def count(ev, _secs, **_kw):
        compiles[0] += ev == COMPILE_EVENT
        compiles[1] += ev == TRACE_EVENT
    jax.monitoring.register_event_duration_secs_listener(count)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import check as C
    from repro.launch.train import use_compile_cache

    cache_dir = use_compile_cache()
    used = devices[:cell.chips]
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)} "
        f"(using {len(used)}), jax {jax.__version__}, cache {cache_dir}")
    parts = {"import_and_backend": time.perf_counter() - T_START}

    t = time.perf_counter()
    traffic = make_traffic(cell, seed)
    parts["traffic"] = time.perf_counter() - t
    t = time.perf_counter()
    prog = Program(cell, seed, used, traffic)
    parts["state_init"] = time.perf_counter() - t
    tr = cell.traffic
    try:
        t = time.perf_counter()
        readings = prog.warm_up(int(cell.check["check_steps"]))
        jax.block_until_ready((prog.trainer.state, prog.trainer.outer))
        parts["warm_up_steps"] = time.perf_counter() - t
        setup_compiles = compiles[0]
        window = _window(prog, seconds, trace, compiles)
    finally:
        prog.close()
    setup_s = window.pop("t0") - T_START
    parts_txt = ", ".join(f"{k} {v:.3f} s" for k, v in parts.items())
    log(f"setup_s {setup_s:.3f}: {parts_txt}; {setup_compiles} programs "
        f"compiled or loaded from the cache")
    peak = _peak_bytes(used)
    del prog
    gc.collect()

    steps, syncs = window["steps"], window["syncs"]
    tokens = steps * tr["global_batch"] * tr["seq_len"]
    tokens_per_s = tokens / window["window_s"]
    iv = sorted(window["intervals"])
    med = iv[len(iv) // 2]
    slow = [x for x in window["intervals"] if x > 2 * med]
    log(f"window {window['window_s']:.3f} s: {steps} steps ({syncs} outer "
        f"syncs), {tokens} tokens, {window['compiles']} compilations, "
        f"{window['traces']} traces; "
        f"{len(iv)} step intervals, median {1e3 * med:.3f} ms, longest "
        f"{1e3 * iv[-1]:.3f} ms, {len(slow)} over twice the median "
        f"({1e3 * sum(slow):.1f} ms in all); peak device memory {peak} bytes")
    failed = sum(not math.isfinite(x) for x in window["losses"])

    metrics = {}
    e2e = {"tokens_per_s": tokens_per_s,
           "step_ms_p95": 1e3 * _percentile(window["intervals"], 95),
           "setup_s": setup_s}
    if not trace:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    result_device = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(devices), "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        ctx = _trace_context(cell, window, tokens_per_s, used, dev)
        result_device["busy_s"] = ctx["busy_s"]
        result_device["window_s"] = window["window_s"]
        for m in cell.per_layer:
            value = _load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = ctx["breakdown"]

    t = time.perf_counter()
    ref = C.reference_readings(cell, seed)
    log(f"reference: {cell.check['check_steps']} steps in "
        f"{time.perf_counter() - t:.3f} s")
    numbers = C.compare(readings, ref, cell.check)
    numbers["window_compiles"] = {"value": window["compiles"], "limit": 0}
    numbers["window_traces"] = {"value": window["traces"], "limit": 0}
    correct = (all(n["value"] <= n["limit"] for n in numbers.values())
               and failed == 0)
    result = {"correct": correct, "attempted": steps, "failed": failed,
              "metrics": metrics, "device": result_device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = numbers
    return result


def _window(prog: Program, seconds: float, trace: bool, compiles) -> dict:
    """The timed window: ``train_step`` back to back for ``seconds``."""
    import jax

    t = prog.trainer
    H = prog.tc.sync_interval
    first = t.step
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    span = (jax.profiler.TraceAnnotation if trace
            else lambda _name: contextlib.nullcontext())
    c0, r0 = compiles
    t0 = time.perf_counter()
    marks, waits, losses = [t0], [], []
    while True:
        a = time.perf_counter()
        with span("bench.next_batch"):
            batch = next(prog.pipeline)
        waits.append(time.perf_counter() - a)
        with span("bench.train_step"):
            losses.append(t.train_step(batch)["loss"])
        marks.append(time.perf_counter())
        elapsed = marks[-1] - t0
        if elapsed >= seconds or (trace and elapsed >= TRACE_MIN_S
                                  and t.step % H == 0):
            break
    with span("bench.block_until_ready"):
        jax.block_until_ready((t.state, t.outer))
    t1 = time.perf_counter()
    n_compiles, n_traces = compiles[0] - c0, compiles[1] - r0
    if trace:
        jax.profiler.stop_trace()
    steps = t.step - first
    syncs = sum((s + 1) % H == 0 for s in range(first, t.step))
    return {"t0": t0, "window_s": t1 - t0, "steps": steps, "syncs": syncs,
            "intervals": [b - a for a, b in zip(marks, marks[1:])],
            "data_waits": waits, "losses": losses, "compiles": n_compiles,
            "traces": n_traces}


def _trace_context(cell: Cell, window: dict, tokens_per_s: float, used,
                   dev) -> dict:
    """What the per-layer readers read: the reduced trace and the counts."""
    import devtrace as T

    red = T.reduce(T.load(TRACE_DIR))
    return {"cell": cell, "window": window, "tokens_per_s": tokens_per_s,
            "chips": len(used), "device_kind": dev.device_kind,
            "trace": red, "busy_s": red.busy_s,
            "breakdown": red.breakdown(), "log": log}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # The compilation cache lives inside the checkout, at a fixed path, and
    # libtpu writes no logs of its own.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        if not (ROOT / "src" / "repro").is_dir():
            raise RunError(f"no program under {ROOT / 'src'}")
        cell = load_cell(args.workload)
        result = run(cell, args.seed, args.seconds, bool(args.trace))
    except RunError as e:
        print(f"bench: FAIL: {e}", file=sys.stderr, flush=True)
        return 2
    for name, n in result["check"].items():
        print(f"check {name} {n['value']:.6g} limit {n['limit']:.6g}",
              file=sys.stderr)
    sys.stderr.flush()
    log(f"run took {time.perf_counter() - T_START:.3f} s")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
