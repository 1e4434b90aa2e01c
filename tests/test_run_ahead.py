"""The trainer's host run-ahead of one step.

``Trainer.train_step`` returns its metrics as device scalars and waits
only for the previous step's. Driven through a sync, it gives every
metric, the final state and the outer state bit for bit as a loop that
reads each step's metrics with ``float`` at once; a returned step is
finished on the device once the next call returns, and the counts of
the waits match the ``trainer.metrics_read`` spans. A tiny model on the
CPU: one group in this process, two groups in a child process with two
host devices (``python tests/test_run_ahead.py 2``).
"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.config import ModelConfig, ParallelConfig, TrainConfig
from repro.launch.train import Trainer

SRC = Path(__file__).resolve().parents[1] / "src"

MC = ModelConfig(num_layers=2, d_model=64, num_heads=2, num_kv_heads=2,
                 d_ff=128, vocab_size=128, dtype="float32",
                 norm="layernorm", activation="gelu", positional="learned",
                 max_position_embeddings=32)
H = 3
STEPS = H + 2  # the sync after step H - 1, then two inner steps
TC = TrainConfig(seq_len=16, global_batch_size=4, optimizer="pier",
                 sync_interval=H, sync_delay=0, warmup_frac=0.0,
                 total_steps=20)


def _trainer(groups: int) -> Trainer:
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:groups]).reshape(groups, 1, 1),
        ("data_outer", "data_inner", "model"))
    pc = ParallelConfig(data_axis_size=groups, model_axis_size=1,
                        data_outer=groups)
    return Trainer(MC, TC, pc, mesh)


def _batches(tr: Trainer, steps: int = STEPS) -> list:
    rng = np.random.default_rng(0)
    out = []
    for _ in range(steps):
        x = rng.integers(0, MC.vocab_size, (TC.global_batch_size, 17),
                         dtype=np.int32)
        b = {"tokens": x[:, :-1], "labels": x[:, 1:]}
        out.append(jax.device_put(b, tr.bundle.batch_sharding(b)))
    return out


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def check_matches_blocking(groups: int) -> None:
    """Run-ahead against reading every step's metrics at once."""
    ahead = _trainer(groups)
    returned = [ahead.train_step(b) for b in _batches(ahead)]
    for m in returned:
        assert sorted(m) == ["grad_norm", "loss", "lr"]
        for v in m.values():
            assert isinstance(v, jax.Array) and v.shape == (), v

    blocking = _trainer(groups)
    read = [{k: float(v) for k, v in blocking.train_step(b).items()}
            for b in _batches(blocking)]
    # each wait found the previous step done: the float had drained it
    assert (blocking.steps_waited, blocking.steps_ahead) == (STEPS - 1, 0)

    assert jax.tree.map(float, jax.device_get(returned)) == read
    _assert_trees_equal(ahead.state, blocking.state)
    _assert_trees_equal(ahead.outer, blocking.outer)


@pytest.mark.parametrize("groups", [1, 2])
def test_matches_blocking_path(groups):
    if groups == 1:
        check_matches_blocking(groups)
        return
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join(
                   [str(SRC), os.environ.get("PYTHONPATH", "")]))
    r = subprocess.run([sys.executable, __file__, str(groups)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "RUN_AHEAD_OK" in r.stdout


def test_previous_step_ready_at_return():
    tr = _trainer(1)
    prev = None
    for n, b in enumerate(_batches(tr)):
        m = tr.train_step(b)
        if prev is not None:
            assert all(v.is_ready() for v in prev.values()), n
        assert tr.steps_waited == n
        assert 0 <= tr.steps_ahead <= tr.steps_waited
        prev = m


def test_counts_match_spans(tmp_path):
    """Every wait is one ``trainer.metrics_read`` span; its ``ahead`` adds
    up to ``steps_ahead``."""
    from jax.profiler import ProfileData

    tr = _trainer(1)
    batches = _batches(tr, 2 * STEPS)
    for b in batches[:STEPS]:  # compile outside the trace
        tr.train_step(b)
    waited, ahead = tr.steps_waited, tr.steps_ahead
    with jax.profiler.trace(str(tmp_path)):
        for b in batches[STEPS:]:
            tr.train_step(b)
    (xplane,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    spans = [dict(e.stats)
             for plane in ProfileData.from_file(str(xplane)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name == "trainer.metrics_read"]
    assert len(spans) == tr.steps_waited - waited == STEPS
    assert sum(int(s["ahead"]) for s in spans) == tr.steps_ahead - ahead


def test_run_returns_float_history():
    tr = _trainer(1)
    batches = _batches(tr)
    history = tr.run(STEPS, iter(batches), log_every=2)
    assert history is tr.history and len(history) == STEPS
    for m in history:
        assert all(type(v) is float for v in m.values()), m

    blocking = _trainer(1)
    assert history == [
        {k: float(v) for k, v in blocking.train_step(b).items()}
        for b in _batches(blocking)]


if __name__ == "__main__":
    check_matches_blocking(int(sys.argv[1]))
    print("RUN_AHEAD_OK")
