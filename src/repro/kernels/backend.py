"""KernelBackend registry: which lowering lane serves each kernel entry.

Four backends (DESIGN.md §13), resolved once per process:

- ``tpu-mosaic``  — compiled Pallas (Mosaic) on real TPU devices.
- ``gpu-triton``  — Pallas-on-Triton lowering for the kernels whose bodies
  are portable (plain ``pl.BlockSpec`` only), with a per-kernel jnp
  fallback for the TPU-idiomatic ones (SMEM scalars, VMEM scratch,
  scalar-prefetch grids, remote DMA — none of which Triton lowers).
- ``interpret``   — ``pallas_call(interpret=True)``: the kernel Python
  bodies execute on the host. The default off-accelerator, and the lane
  every bitwise kernel-vs-oracle test pins.
- ``jnp-ref``     — the :mod:`repro.kernels.ref` oracles as a dispatchable
  lane: a full training/serving step with no Pallas anywhere (CI's
  backend-matrix job proves it).

Resolution order: :func:`set_kernel_backend` (the launcher's
``--kernel-backend``) > the ``REPRO_KERNEL_BACKEND`` env var > platform
auto-detect. It happens lazily at the first kernel call — never at import
time, so ``jax_platform_name`` / distributed init can still run first —
and :func:`reset_backend_cache` drops the cached answer (tests, and any
launcher that re-initializes the platform).

Kernels keep their ``interpret: Optional[bool] = None`` signatures: an
explicit bool is the legacy per-call override (always the Pallas body,
interpreted or compiled as requested — the bitwise test harness);
``None`` dispatches through :func:`resolve_kernel`.

Lives in its own module (not ``ops.py``) because the kernel modules
cannot import ``ops`` without a cycle.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Mapping, Optional, Tuple

import jax

# The three lanes a kernel entry point can resolve to.
COMPILED = "pallas-compiled"    # pl.pallas_call, compiled lowering
INTERPRET = "pallas-interpret"  # pl.pallas_call(interpret=True)
JNP = "jnp"                     # the kernels/ref.py oracle

BACKEND_NAMES = ("tpu-mosaic", "gpu-triton", "interpret", "jnp-ref")

# Per-kernel capability table: which lane serves each kernel on each
# backend, and why (DESIGN.md §13 carries the prose version).
#
# gpu-triton column: quantize/dequantize/rmsnorm use only plain
# ``pl.BlockSpec`` tiling — portable to the Triton lowering. The rest are
# TPU-idiomatic and fall back to the jnp oracle there:
#   pier_update       — (1,1) μ/lr scalars in ``pltpu.SMEM``
#   flash_attention   — ``pltpu.VMEM`` scratch + TPU dimension_semantics
#   decode_attention  — ``pltpu.PrefetchScalarGridSpec`` block-table gather
#   ring_allreduce    — ``pltpu.make_async_remote_copy`` remote DMA
# interpret column: every kernel body executes under the interpreter —
# except the remote-DMA ring, whose semantics need a real multi-device
# TPU ring (the transport resolver picks the XLA collective instead, see
# kernels/ring_allreduce.resolve_transport).
# tpu-mosaic column: the remote-DMA ring does not compile for the chip
# yet (Mosaic cannot prove its dynamic per-source row store aligned to the
# int8 (4, 128) tile), so the wire exchange takes the XLA collective there
# too until it does.
KERNEL_CAPS: Mapping[str, Mapping[str, str]] = {
    "quantize": {
        "tpu-mosaic": COMPILED, "gpu-triton": COMPILED,
        "interpret": INTERPRET, "jnp-ref": JNP,
    },
    "dequantize": {
        "tpu-mosaic": COMPILED, "gpu-triton": COMPILED,
        "interpret": INTERPRET, "jnp-ref": JNP,
    },
    "rmsnorm": {
        "tpu-mosaic": COMPILED, "gpu-triton": COMPILED,
        "interpret": INTERPRET, "jnp-ref": JNP,
    },
    "pier_update": {
        "tpu-mosaic": COMPILED, "gpu-triton": JNP,
        "interpret": INTERPRET, "jnp-ref": JNP,
    },
    "flash_attention": {
        "tpu-mosaic": COMPILED, "gpu-triton": JNP,
        "interpret": INTERPRET, "jnp-ref": JNP,
    },
    "decode_attention": {
        "tpu-mosaic": COMPILED, "gpu-triton": JNP,
        "interpret": INTERPRET, "jnp-ref": JNP,
    },
    "ring_allreduce": {
        "tpu-mosaic": JNP, "gpu-triton": JNP,
        "interpret": JNP, "jnp-ref": JNP,
    },
}


@dataclass(frozen=True)
class KernelBackend:
    """One resolved backend: a name and its column of the capability table."""

    name: str

    def lane(self, kernel: str) -> str:
        try:
            return KERNEL_CAPS[kernel][self.name]
        except KeyError:
            raise KeyError(
                f"unknown kernel {kernel!r} "
                f"(registered: {', '.join(sorted(KERNEL_CAPS))})") from None


BACKENDS: Mapping[str, KernelBackend] = {
    name: KernelBackend(name) for name in BACKEND_NAMES}

# Module-level cache (NOT functools.cache: an explicit reset must be able
# to drop an answer cached before jax_platform_name / distributed init).
_forced: Optional[str] = None
_resolved: Optional[KernelBackend] = None
_is_tpu: Optional[bool] = None


def _detect_platform() -> str:
    """The jax platform — the only place kernels touch device state.

    Called lazily at the first kernel dispatch (never at import time).
    The single monkeypatch seam for the fake-platform tests. A backend
    that fails to initialize raises here: guessing ``"cpu"`` would run
    every kernel in interpret mode on a machine that has a chip.
    """
    return jax.devices()[0].platform


def default_backend_name() -> str:
    platform = _detect_platform()
    if platform == "tpu":
        return "tpu-mosaic"
    if platform in ("gpu", "cuda", "rocm"):
        return "gpu-triton"
    return "interpret"


def resolve_backend() -> KernelBackend:
    """The process-wide backend, resolved once and cached.

    Order: :func:`set_kernel_backend` override > ``REPRO_KERNEL_BACKEND``
    env var > platform auto-detect. :func:`reset_backend_cache` drops the
    cached answer so the next call re-resolves.
    """
    global _resolved
    if _resolved is None:
        name = (_forced
                or os.environ.get("REPRO_KERNEL_BACKEND", "").strip()
                or default_backend_name())
        if name not in BACKENDS:
            raise ValueError(
                f"unknown kernel backend {name!r} "
                f"(choices: {', '.join(BACKEND_NAMES)})")
        _resolved = BACKENDS[name]
    return _resolved


def set_kernel_backend(name: Optional[str]) -> None:
    """Force the backend process-wide (the launcher's ``--kernel-backend``).

    ``None``/``""``/``"auto"`` reverts to env-var/auto-detect resolution.
    Clears the cached resolution either way, so the change takes effect at
    the next kernel call.
    """
    global _forced
    if name in (None, "", "auto"):
        _forced = None
    elif name not in BACKENDS:
        raise ValueError(
            f"unknown kernel backend {name!r} "
            f"(choices: {', '.join(BACKEND_NAMES)})")
    else:
        _forced = name
    reset_backend_cache()


def reset_backend_cache() -> None:
    """Drop the cached backend resolution and platform answer.

    Required after anything that changes what ``jax.devices()`` reports —
    ``jax.config.update("jax_platform_name", ...)``, distributed init —
    and by tests that fake the platform. Does NOT clear an explicit
    :func:`set_kernel_backend` override (that is a user decision, not a
    cache).
    """
    global _resolved, _is_tpu
    _resolved = None
    _is_tpu = None


def on_tpu() -> bool:
    """Whether this process runs on real TPU devices (lazily cached)."""
    global _is_tpu
    if _is_tpu is None:
        _is_tpu = _detect_platform() == "tpu"
    return _is_tpu


def out_struct(shape, dtype, *operands, invariant_over=()) -> jax.ShapeDtypeStruct:
    """``out_shape`` entry of a ``pallas_call`` that may run in ``shard_map``.

    jax's varying-mesh-axes (VMA) check needs every kernel output typed
    with the manual axes it varies over: here the union of the operands'
    axes, less ``invariant_over`` (axes the kernel itself makes every
    device agree on, e.g. a ring all-gather's). Outside ``shard_map`` the
    set is empty and ignored.
    """
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype,
                                vma=vma - frozenset(invariant_over))


def mosaic_call(fn, *args, interpret: bool = False):
    """``fn(*args)`` where jax can lower the Mosaic kernel inside ``fn``.

    jax lowers a compiled Pallas kernel only where every mesh axis is
    manual (or on one device). Inside the Pier steps' partial-manual
    ``shard_map`` the in-group axes are still auto (and under a mesh
    context with no ``shard_map``, all of them are), so a nested
    ``shard_map`` makes them manual, with the operands and results
    replicated over them: over axes of size 1 that changes nothing; over
    larger ones XLA gathers the operands and every device runs the whole
    kernel (:func:`kernel_replicated_axes` names those axes). Interpret
    mode lowers to plain HLO and needs none of this.
    """
    from jax.sharding import AxisType, PartitionSpec

    mesh = jax.sharding.get_abstract_mesh()
    auto = tuple(n for n, t in zip(mesh.axis_names, mesh.axis_types)
                 if t == AxisType.Auto)
    if interpret or not auto:
        return fn(*args)
    return jax.shard_map(fn, in_specs=PartitionSpec(),
                         out_specs=PartitionSpec(), axis_names=set(auto))(*args)


def kernel_replicated_axes(mesh, manual) -> Tuple[str, ...]:
    """Axes of size > 1 over which :func:`mosaic_call` replicates kernels
    run inside a ``shard_map`` manual over ``manual``."""
    return tuple(a for a in mesh.axis_names
                 if a not in manual and mesh.shape[a] > 1)


def kernel_lane(kernel: str) -> str:
    """The resolved backend's lane for one kernel (capability table row)."""
    return resolve_backend().lane(kernel)


def resolve_kernel(kernel: str,
                   interpret: Optional[bool] = None) -> Tuple[str, bool]:
    """``(impl, interpret_flag)`` for one kernel entry point.

    ``impl`` is ``"pallas"`` (run the Pallas body with the returned
    ``interpret`` flag) or ``"jnp"`` (dispatch to the kernels/ref.py
    oracle; the flag is meaningless then). An explicit ``interpret`` bool
    keeps the legacy per-call override: always the Pallas body,
    interpreted or compiled as requested — the bitwise kernel-vs-oracle
    tests pin ``interpret=True`` regardless of the resolved backend.
    """
    if interpret is not None:
        return "pallas", bool(interpret)
    lane = kernel_lane(kernel)
    if lane == JNP:
        return "jnp", False
    return "pallas", lane == INTERPRET


def default_interpret(interpret: Optional[bool] = None) -> bool:
    """Deprecated pre-registry resolver (None -> interpret off-TPU).

    Kept only for backward compatibility; every kernel entry point now
    dispatches through :func:`resolve_kernel`, and no call site outside
    this module remains (asserted by tests/test_backend.py).
    """
    if interpret is None:
        return not on_tpu()
    return interpret
