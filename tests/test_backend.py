"""KernelBackend registry, backend-aware transports, and env presets.

Covers the DESIGN.md §13 contract: lazy per-process resolution
(env var / forced override / platform auto-detect, with an explicit cache
reset), the per-kernel capability table, jnp-ref <-> interpret parity, the
no-Pallas guarantee of the jnp-ref lane, backend-aware wire-transport
resolution, append-only env presets, and the acceptance criterion that no
``default_interpret`` call site survives outside ``kernels/backend.py``.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import backend as kb
from repro.kernels import ops as kops
from repro.kernels.decode_attention import (paged_decode_attention,
                                            paged_decode_attention_ref)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.pier_update import pier_update
from repro.kernels.quantize import dequantize_blockwise, quantize_blockwise
from repro.kernels.ring_allreduce import resolve_transport
from repro.kernels.rmsnorm import rmsnorm
from repro.launch.mesh import GPU_XLA_FLAGS, _merge_xla_flags, apply_env_preset


@pytest.fixture(autouse=True)
def _restore_backend():
    """Every test leaves the process-global backend state as it found it."""
    forced = kb._forced
    yield
    kb._forced = forced
    kb.reset_backend_cache()


def _fake_platform(monkeypatch, platform: str):
    monkeypatch.setattr(kb, "_detect_platform", lambda: platform)
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    kb.set_kernel_backend(None)  # clear any forced override + cache


# ---------------------------------------------------------------------------
# resolution: lazy, env-overridable, resettable
# ---------------------------------------------------------------------------


def test_default_resolution_matches_env_or_platform():
    kb.reset_backend_cache()
    expected = (os.environ.get("REPRO_KERNEL_BACKEND", "").strip()
                or kb.default_backend_name())
    assert kb.resolve_backend().name == expected


def test_env_var_override_and_reset(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "jnp-ref")
    kb.set_kernel_backend(None)
    assert kb.resolve_backend().name == "jnp-ref"
    assert kb.resolve_kernel("quantize") == ("jnp", False)
    # the resolution is cached: flipping the env var without a reset
    # changes nothing until reset_backend_cache drops the cache
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    assert kb.resolve_backend().name == "jnp-ref"
    kb.reset_backend_cache()
    assert kb.resolve_backend().name == "interpret"


def test_invalid_backend_names_raise(monkeypatch):
    with pytest.raises(ValueError, match="unknown kernel backend"):
        kb.set_kernel_backend("cuda-graphs")
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "warp-drive")
    kb.set_kernel_backend(None)
    with pytest.raises(ValueError, match="unknown kernel backend"):
        kb.resolve_backend()


def test_forced_override_beats_env(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    kb.set_kernel_backend("jnp-ref")
    assert kb.resolve_backend().name == "jnp-ref"
    # reset_backend_cache must NOT clear the explicit override (it is a
    # user decision, not a cache)
    kb.reset_backend_cache()
    assert kb.resolve_backend().name == "jnp-ref"
    kb.set_kernel_backend(None)
    assert kb.resolve_backend().name == "interpret"


def test_on_tpu_is_lazily_cached_until_reset(monkeypatch):
    _fake_platform(monkeypatch, "cpu")
    assert kb.on_tpu() is False
    # the answer is pinned until an explicit reset — exactly the
    # functools.cache bug, but now with a documented escape hatch
    monkeypatch.setattr(kb, "_detect_platform", lambda: "tpu")
    assert kb.on_tpu() is False
    kb.reset_backend_cache()
    assert kb.on_tpu() is True


def test_unknown_kernel_raises():
    with pytest.raises(KeyError, match="unknown kernel"):
        kb.resolve_backend().lane("conv3d")


# ---------------------------------------------------------------------------
# capability table: per-platform lanes
# ---------------------------------------------------------------------------


def test_fake_tpu_resolves_compiled_flash_attention(monkeypatch):
    # regression for the hardcoded ``interpret: bool = True`` default:
    # on a TPU platform the resolved lane must be the COMPILED Pallas body
    _fake_platform(monkeypatch, "tpu")
    assert kb.resolve_backend().name == "tpu-mosaic"
    assert kb.resolve_kernel("flash_attention") == ("pallas", False)
    assert kb.resolve_kernel("quantize") == ("pallas", False)
    assert kb.resolve_kernel("decode_attention") == ("pallas", False)
    import inspect

    for fn in (flash_attention, rmsnorm):
        assert inspect.signature(fn).parameters["interpret"].default is None


def test_fake_gpu_lanes(monkeypatch):
    _fake_platform(monkeypatch, "gpu")
    assert kb.resolve_backend().name == "gpu-triton"
    # plain-BlockSpec kernels compile through the Triton lowering
    assert kb.resolve_kernel("quantize") == ("pallas", False)
    assert kb.resolve_kernel("rmsnorm") == ("pallas", False)
    # TPU-idiomatic kernels fall back to the jnp oracle
    assert kb.resolve_kernel("pier_update")[0] == "jnp"
    assert kb.resolve_kernel("flash_attention")[0] == "jnp"
    assert kb.resolve_kernel("decode_attention")[0] == "jnp"
    assert kb.kernel_lane("ring_allreduce") == kb.JNP


def test_explicit_interpret_bool_overrides_lane():
    # the legacy per-call override: an explicit bool always runs the
    # Pallas body (the bitwise kernel-vs-oracle harness pins True)
    kb.set_kernel_backend("jnp-ref")
    assert kb.resolve_kernel("quantize", True) == ("pallas", True)
    assert kb.resolve_kernel("quantize", False) == ("pallas", False)


# ---------------------------------------------------------------------------
# jnp-ref lane: parity with interpret, and zero Pallas calls
# ---------------------------------------------------------------------------


def test_jnp_ref_parity_with_interpret():
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(1000), jnp.float32)
    mom = jnp.asarray(rs.randn(1000), jnp.float32)
    dlt = jnp.asarray(rs.randn(1000), jnp.float32)
    kb.set_kernel_backend("jnp-ref")
    q_j, s_j = quantize_blockwise(x, bits=8, block=256)
    d_j = dequantize_blockwise(q_j, s_j, block=256)
    p_j, m_j = pier_update(x, mom, dlt, jnp.float32(0.9), jnp.float32(0.7))
    kb.set_kernel_backend("interpret")
    q_i, s_i = quantize_blockwise(x, bits=8, block=256)
    d_i = dequantize_blockwise(q_i, s_i, block=256)
    p_i, m_i = pier_update(x, mom, dlt, jnp.float32(0.9), jnp.float32(0.7))
    # the quantizer round trip is bitwise across lanes (the kernel body
    # and the oracle run the same reciprocal-multiply graph)
    np.testing.assert_array_equal(np.asarray(q_j), np.asarray(q_i))
    np.testing.assert_array_equal(np.asarray(s_j), np.asarray(s_i))
    np.testing.assert_array_equal(np.asarray(d_j), np.asarray(d_i))
    np.testing.assert_allclose(np.asarray(p_j), np.asarray(p_i), atol=1e-6)

    B, S, H, hd = 1, 32, 2, 16
    q3 = jnp.asarray(rs.randn(B, S, H, hd), jnp.float32)
    k3 = jnp.asarray(rs.randn(B, S, H, hd), jnp.float32)
    v3 = jnp.asarray(rs.randn(B, S, H, hd), jnp.float32)
    kb.set_kernel_backend("jnp-ref")
    o_j = flash_attention(q3, k3, v3)
    n_j = rmsnorm(q3.reshape(-1, hd), jnp.ones((hd,), jnp.float32))
    kb.set_kernel_backend("interpret")
    o_i = flash_attention(q3, k3, v3)
    n_i = rmsnorm(q3.reshape(-1, hd), jnp.ones((hd,), jnp.float32))
    np.testing.assert_allclose(np.asarray(o_j), np.asarray(o_i), atol=2e-6)
    np.testing.assert_allclose(np.asarray(n_j), np.asarray(n_i), atol=1e-6)


def test_jnp_ref_decode_matches_oracle():
    rs = np.random.RandomState(1)
    B, H, hd, N, bs, T = 2, 2, 8, 6, 4, 3
    q = jnp.asarray(rs.randn(B, H, hd), jnp.float32)
    kp = jnp.asarray(rs.randn(N, bs, H, hd), jnp.float32)
    vp = jnp.asarray(rs.randn(N, bs, H, hd), jnp.float32)
    bt = jnp.asarray(rs.randint(0, N, (B, T)), jnp.int32)
    cl = jnp.asarray([5, 9], jnp.int32)
    kb.set_kernel_backend("jnp-ref")
    out = paged_decode_attention(q, kp, vp, bt, cl)
    ref = paged_decode_attention_ref(q, kp, vp, bt, cl)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_jnp_ref_needs_no_pallas(monkeypatch):
    """Every ops.py entry point runs with pallas_call stubbed to raise."""
    from jax.experimental import pallas as pl_mod

    def boom(*a, **k):
        raise AssertionError("pallas_call invoked on the jnp-ref lane")

    kb.set_kernel_backend("jnp-ref")
    monkeypatch.setattr(pl_mod, "pallas_call", boom)
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(512), jnp.float32)
    q, s = kops.quantize_blockwise(x, bits=8, block=128)
    kops.dequantize_blockwise(q, s, block=128)
    B, S, H, hd = 1, 16, 2, 8
    t = jnp.asarray(rs.randn(B, S, H, hd), jnp.float32)
    kops.flash_attention(t, t, t)
    kops.rmsnorm(t, jnp.ones((hd,), jnp.float32))
    kp = jnp.asarray(rs.randn(4, 4, H, hd), jnp.float32)
    kops.paged_decode_attention(
        jnp.asarray(rs.randn(B, H, hd), jnp.float32), kp, kp,
        jnp.zeros((B, 2), jnp.int32), jnp.asarray([3], jnp.int32))
    pier_update(x, x, x, jnp.float32(0.9), jnp.float32(0.5))
    # the compressed outer pipeline's pallas entry too (quant_fns)
    from repro.core.outer import compress_delta

    compress_delta(t.reshape(-1), None, bits=8, block=64, use_pallas=True)


# ---------------------------------------------------------------------------
# backend-aware transport resolution
# ---------------------------------------------------------------------------


def test_transport_off_tpu_is_collective():
    assert resolve_transport(axis_names=("data_outer",)) == "ring"
    assert resolve_transport(axis_names=("pod", "data_outer")) == "ring"


def test_transport_dma_needs_tpu_and_compiled_lane(monkeypatch):
    fallback = "ring"
    _fake_platform(monkeypatch, "tpu")
    # the remote-DMA ring does not compile for the chip yet: its tpu-mosaic
    # lane is the jnp one, so a TPU takes the collective transport
    assert kb.kernel_lane("ring_allreduce") == kb.JNP
    assert resolve_transport(axis_names=("data_outer",)) == fallback
    # with the lane compiled, a single-axis TPU exchange takes the ring
    caps = dict(kb.KERNEL_CAPS)
    caps["ring_allreduce"] = {**caps["ring_allreduce"],
                              "tpu-mosaic": kb.COMPILED}
    monkeypatch.setattr(kb, "KERNEL_CAPS", caps)
    assert resolve_transport(axis_names=("data_outer",)) == "dma"
    # dma never spans multiple exchange axes, never runs without pallas
    assert resolve_transport(
        axis_names=("pod", "data_outer")) == fallback
    assert resolve_transport(
        axis_names=("data_outer",), use_pallas=False) == fallback
    # backend-aware: an interpret/jnp-ref override disables dma even on
    # real TPU hardware (its ring_allreduce lane is not COMPILED there)
    kb.set_kernel_backend("interpret")
    assert resolve_transport(axis_names=("data_outer",)) == fallback
    # a forced tpu-mosaic backend off-TPU still falls back (on_tpu gate)
    _fake_platform(monkeypatch, "cpu")
    kb.set_kernel_backend("tpu-mosaic")
    assert resolve_transport(axis_names=("data_outer",)) == fallback


def test_sync_plans_name_their_transport():
    from repro.sync.strategies import Chunked, FlatFP32, Int8Wire

    pshapes = {"w": jax.ShapeDtypeStruct((64,), jnp.float32)}
    assert FlatFP32().plan(pshapes, None).transport == "collective"
    assert Int8Wire().plan(pshapes, None).transport == "ring"
    assert Chunked(inner=Int8Wire(), num_chunks=2).plan(
        pshapes, None).transport == "ring"


# ---------------------------------------------------------------------------
# env-preset hygiene (append, idempotent, conflict no-op)
# ---------------------------------------------------------------------------


def test_gpu_preset_appends_to_existing_xla_flags():
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    r = apply_env_preset("gpu-triton", env=env)
    flags = env["XLA_FLAGS"].split()
    # the user's flag survives, in place, ahead of the preset's
    assert flags[0] == "--xla_force_host_platform_device_count=8"
    for f in GPU_XLA_FLAGS:
        assert f in flags
    assert r["xla_flags_appended"] == list(GPU_XLA_FLAGS)
    assert r["xla_flags_skipped"] == []


def test_env_preset_is_idempotent():
    env = {}
    apply_env_preset("gpu-triton", env=env)
    before = dict(env)
    r2 = apply_env_preset("gpu-triton", env=env)
    assert env == before
    assert r2["xla_flags_appended"] == []
    assert r2["xla_flags_skipped"] == list(GPU_XLA_FLAGS)
    assert r2["env_set"] == {}


def test_env_preset_noops_on_conflicting_flag():
    # the user disabled async collectives explicitly: the preset must not
    # add a second (winning) occurrence or rewrite the value
    user = "--xla_gpu_enable_async_collectives=false"
    env = {"XLA_FLAGS": user}
    r = apply_env_preset("gpu-triton", env=env)
    assert env["XLA_FLAGS"].split().count(user) == 1
    assert "--xla_gpu_enable_async_collectives=true" not in env["XLA_FLAGS"]
    assert "--xla_gpu_enable_async_collectives=true" in r["xla_flags_skipped"]


def test_host_device_count_preset():
    env = {}
    apply_env_preset("jnp-ref", env=env, host_device_count=4)
    assert env["XLA_FLAGS"] == "--xla_force_host_platform_device_count=4"
    # user already forced a count: preset defers
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    r = apply_env_preset("interpret", env=env, host_device_count=4)
    assert env["XLA_FLAGS"] == "--xla_force_host_platform_device_count=8"
    assert r["xla_flags_appended"] == []
    # accelerator lanes never force the host platform count
    env = {}
    apply_env_preset("tpu-mosaic", env=env, host_device_count=4)
    assert "XLA_FLAGS" not in env


def test_env_preset_rejects_unknown_backend():
    with pytest.raises(ValueError, match="unknown kernel backend"):
        apply_env_preset("rocm")


def test_merge_xla_flags_pure():
    merged, appended, skipped = _merge_xla_flags(
        "--a=1 --b=2", ["--b=3", "--c=4"])
    assert merged == "--a=1 --b=2 --c=4"
    assert appended == ["--c=4"] and skipped == ["--b=3"]


# ---------------------------------------------------------------------------
# acceptance: no default_interpret call sites outside backend.py
# ---------------------------------------------------------------------------


def test_no_default_interpret_callsites_outside_backend():
    import repro

    pkg = list(repro.__path__)[0]
    offenders = []
    for root, _dirs, files in os.walk(pkg):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            if os.path.join("kernels", "backend.py") in path:
                continue
            with open(path) as f:
                if "default_interpret" in f.read():
                    offenders.append(os.path.relpath(path, pkg))
    assert not offenders, offenders
