"""Production mesh construction and the Pier group refinement.

``make_production_mesh`` builds the spec-mandated meshes:

    single pod : (16, 16)      axes (data, model)   — 256 chips (v5e pod)
    multi-pod  : (2, 16, 16)   axes (pod, data, model) — 512 chips

``refine_mesh`` splits the data axis into (data_outer, data_inner) for Pier's
group structure **without touching device order**, so shardings remain
device-consistent: a Pier group = one (pod, data_outer) index =
``data_inner × model`` chips, a contiguous mesh slice with full intra-group
ICI bandwidth. All functions (not module constants) — importing this module
never touches jax device state.

Also home to the backend-aware *environment presets*
(:func:`apply_env_preset`): the async-collective / latency-hiding XLA
flags, tcmalloc hints, and host-device-count settings each kernel backend
wants, applied by the launcher **before** jax initializes its backends.
Presets only ever *append* — a flag name the user already set is left
untouched (:func:`_merge_xla_flags`), and double-apply is a no-op.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Tuple

from jax.sharding import Mesh

from repro import compat


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def refine_mesh(mesh: Mesh, data_outer: int) -> Mesh:
    """(pod?, data, model) -> (pod?, data_outer, data_inner, model)."""
    names = mesh.axis_names
    devs = mesh.devices
    if "pod" in names:
        pod, data, model = devs.shape
        assert data % data_outer == 0, (data, data_outer)
        new = devs.reshape(pod, data_outer, data // data_outer, model)
        axes = ("pod", "data_outer", "data_inner", "model")
    else:
        data, model = devs.shape
        assert data % data_outer == 0, (data, data_outer)
        new = devs.reshape(data_outer, data // data_outer, model)
        axes = ("data_outer", "data_inner", "model")
    return compat.mesh_from_devices(new, axes)


def make_pier_mesh(
    *,
    multi_pod: bool = False,
    data_outer: int = 4,
) -> Mesh:
    return refine_mesh(make_production_mesh(multi_pod=multi_pod), data_outer)


def small_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Arbitrary mesh over host devices (tests / CPU runs)."""
    return compat.make_mesh(shape, axes)


def manual_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The axes Pier relaxes: everything outer to the group."""
    return tuple(a for a in ("pod", "data_outer") if a in mesh.axis_names)


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data_outer", "data_inner", "data")
                 if a in mesh.axis_names)


def axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


# ---------------------------------------------------------------------------
# backend-aware environment presets (applied before jax initializes)
# ---------------------------------------------------------------------------

# Async-collective / latency-hiding flags for the gpu-triton lane: make
# XLA:GPU overlap the outer collectives with inner compute (the whole
# point of sync_delay) and route softmax/gemm through Triton. Names only
# matter for conflict detection — a user's explicit value always wins.
GPU_XLA_FLAGS: Tuple[str, ...] = (
    "--xla_gpu_enable_triton_softmax_fusion=true",
    "--xla_gpu_triton_gemm_any=True",
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)

# tcmalloc: LD_PRELOAD cannot take effect inside an already-running
# process, so the preset only *reports* a discovered library path for a
# wrapper script to export; the large-alloc report threshold is a plain
# env var (silences the per-arena warnings at multi-GiB host staging).
TCMALLOC_PRELOAD_PATHS: Tuple[str, ...] = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/libtcmalloc.so.4",
)
TCMALLOC_REPORT_THRESHOLD = "10737418240"  # 10 GiB


def _merge_xla_flags(
        existing: str,
        additions: Sequence[str]) -> Tuple[str, List[str], List[str]]:
    """Append ``additions`` to an XLA_FLAGS string without clobbering.

    Returns ``(merged, appended, skipped)``. A flag whose *name* (the
    part before ``=``) already appears in ``existing`` is skipped — the
    user's value wins even when it conflicts with the preset, and
    re-applying the same preset is a no-op (idempotent). Wholesale
    ``os.environ["XLA_FLAGS"] = ...`` assignment (the pattern common in
    GPU launch scripts) silently drops whatever was already set — e.g.
    CI's ``--xla_force_host_platform_device_count`` — which is exactly
    the bug this helper exists to prevent.
    """
    tokens = existing.split()
    have = {t.split("=", 1)[0] for t in tokens}
    appended: List[str] = []
    skipped: List[str] = []
    for flag in additions:
        name = flag.split("=", 1)[0]
        if name in have:
            skipped.append(flag)
            continue
        tokens.append(flag)
        have.add(name)
        appended.append(flag)
    return " ".join(tokens), appended, skipped


def apply_env_preset(backend: str, *, env=None,
                     host_device_count: Optional[int] = None) -> Dict:
    """Apply one kernel backend's environment preset, append-only.

    Must run before jax initializes its backends (XLA_FLAGS is read once
    at backend init); the launcher calls it at the top of ``main()`` when
    an explicit ``--kernel-backend`` is given. ``env`` defaults to
    ``os.environ`` (pass a dict in tests). ``host_device_count`` adds
    ``--xla_force_host_platform_device_count`` for the host-platform
    lanes (interpret / jnp-ref) so multi-device meshes work on CPU.

    Returns a report dict: ``xla_flags_appended`` / ``xla_flags_skipped``
    (conflicts left to the user's value), ``env_set``, and
    ``ld_preload_hint`` (a discovered tcmalloc path, never exported here
    — preloading must happen in the wrapper script). Never touches jax
    device state.
    """
    known = ("tpu-mosaic", "gpu-triton", "interpret", "jnp-ref")
    if backend not in known:
        raise ValueError(
            f"unknown kernel backend {backend!r} (choices: {', '.join(known)})")
    if env is None:
        env = os.environ
    additions: List[str] = []
    if backend == "gpu-triton":
        additions += list(GPU_XLA_FLAGS)
    if host_device_count is not None and backend in ("interpret", "jnp-ref"):
        additions.append(
            f"--xla_force_host_platform_device_count={int(host_device_count)}")
    merged, appended, skipped = _merge_xla_flags(
        env.get("XLA_FLAGS", ""), additions)
    if appended:
        env["XLA_FLAGS"] = merged
    env_set: Dict[str, str] = {}
    if (backend in ("gpu-triton", "tpu-mosaic")
            and "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD" not in env):
        env["TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD"] = (
            TCMALLOC_REPORT_THRESHOLD)
        env_set["TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD"] = (
            TCMALLOC_REPORT_THRESHOLD)
    ld_preload_hint = None
    if backend in ("gpu-triton", "tpu-mosaic") and "LD_PRELOAD" not in env:
        for path in TCMALLOC_PRELOAD_PATHS:
            if os.path.exists(path):
                ld_preload_hint = path
                break
    return {
        "backend": backend,
        "xla_flags_appended": appended,
        "xla_flags_skipped": skipped,
        "env_set": env_set,
        "ld_preload_hint": ld_preload_hint,
    }
