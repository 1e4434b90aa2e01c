"""The program's trace names: device scopes, host spans and kernel names.

The jitted steps carry ``jax.named_scope`` scopes that a profiler trace
shows on every device op (``pier.blocks``, ``pier.head``,
``pier.optimizer``, ``pier.outer.delta``, ``pier.outer.exchange``,
``pier.outer.update``); autodiff wraps a scope as ``jvp(...)`` in the
forward pass and ``transpose(jvp(...))`` in the backward. The trainer and
the input pipeline record ``jax.profiler.TraceAnnotation`` host spans, and
every Pallas kernel has an explicit name. A tiny model on one CPU device.
"""

import ast
import gc
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ModelConfig, ParallelConfig, TrainConfig
from repro.data.pipeline import DataPipeline
from repro.launch import mesh as M
from repro.launch.train import Trainer

KERNELS = Path(__file__).resolve().parents[1] / "src" / "repro" / "kernels"

MC = ModelConfig(num_layers=2, d_model=64, num_heads=2, num_kv_heads=2,
                 d_ff=128, vocab_size=128, dtype="float32",
                 norm="layernorm", activation="gelu", positional="learned",
                 max_position_embeddings=32)
TC = TrainConfig(seq_len=16, global_batch_size=2, optimizer="pier",
                 sync_interval=2, sync_delay=0, warmup_frac=0.0,
                 total_steps=20)


@pytest.fixture(scope="module")
def trainer():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                             ("data_outer", "data_inner", "model"))
    pc = ParallelConfig(data_axis_size=1, model_axis_size=1, data_outer=1)
    rng = np.random.default_rng(0)

    def make_batch(_step):
        x = rng.integers(0, MC.vocab_size, (2, 17)).astype(np.int32)
        return {"tokens": x[:, :-1], "labels": x[:, 1:]}

    tr = Trainer(MC, TC, pc, mesh)
    pipe = DataPipeline(mesh, M.data_axes(mesh), make_batch)
    yield tr, pipe
    pipe.close()


def _op_names(compiled_text: str) -> list:
    return re.findall(r'op_name="([^"]*)"', compiled_text)


@pytest.fixture(scope="module")
def inner_ops(trainer):
    tr, pipe = trainer
    return _op_names(tr.bundle.inner_step.lower(
        tr.state, next(pipe), jnp.asarray(0, jnp.int32)).compile().as_text())


@pytest.fixture(scope="module")
def outer_ops(trainer):
    tr, _ = trainer
    return _op_names(tr.bundle.outer_step.lower(
        tr.state, tr.outer, jnp.float32(0.9),
        jnp.float32(0.7)).compile().as_text())


@pytest.mark.parametrize("wrapped", [
    "jvp(pier.blocks)", "transpose(jvp(pier.blocks))",
    "jvp(pier.head)", "transpose(jvp(pier.head))", "pier.optimizer"])
def test_inner_step_scopes(inner_ops, wrapped):
    assert any(wrapped in p.split("/") for p in inner_ops), wrapped


def test_inner_step_forward_and_backward_split(inner_ops):
    fwd = [p for p in inner_ops if "jvp(pier.blocks)" in p.split("/")]
    assert fwd and not any("transpose(" in p for p in fwd)


@pytest.mark.parametrize("scope", [
    "pier.outer.delta", "pier.outer.exchange", "pier.outer.update"])
def test_outer_step_scopes(outer_ops, scope):
    assert any(scope in p.split("/") for p in outer_ops), scope


@pytest.fixture(scope="module")
def host_spans(trainer, tmp_path_factory):
    """The host spans of two train steps across an outer sync: each
    name with the names of its arguments."""
    from jax.profiler import ProfileData

    tr, pipe = trainer
    tr.train_step(next(pipe))  # compile outside the trace
    tr.train_step(next(pipe))
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        for _ in range(2):  # the second ends at the sync (step 3 of H 2)
            tr.train_step(next(pipe))
        gc.collect()
    (xplane,) = out.glob("plugins/profile/*/*.xplane.pb")
    spans = {}
    for plane in ProfileData.from_file(str(xplane)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    spans.setdefault(e.name, set()).update(
                        name for name, _ in e.stats)
    return spans


# The arguments a span carries beyond its name, where a reader needs them.
SPAN_ARGS = {"trainer.metrics_read": {"step", "ahead"}}


@pytest.mark.parametrize("span", [
    "trainer.dispatch", "trainer.outer", "trainer.metrics_read",
    "pipeline.queue_wait", "pipeline.device_put", "python.gc"])
def test_host_spans(host_spans, span):
    assert span in host_spans
    assert SPAN_ARGS.get(span, set()) <= host_spans[span]


def _pallas_calls():
    calls = []
    for path in sorted(KERNELS.glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                calls.append(pytest.param(
                    node, id=f"{path.name}:{node.lineno}"))
    return calls


@pytest.mark.parametrize("call", _pallas_calls())
def test_pallas_call_is_named(call):
    names = [k.value for k in call.keywords if k.arg == "name"]
    assert names and isinstance(names[0], ast.Constant)


def test_kernel_names():
    """The names the trace shows; the flash forward keeps the name its
    roofline reader matches."""
    names = {k.value.value for p in _pallas_calls()
             for k in p.values[0].keywords if k.arg == "name"}
    assert names == {"pier_update", "rmsnorm", "quantize", "dequantize",
                     "decode_attention", "_flash_attention_pallas",
                     "ring_allgather", "shard_scatter"}
