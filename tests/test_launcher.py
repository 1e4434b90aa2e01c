"""Launcher set-up that needs no device: mesh resolution, the Pallas switch
and the compile-cache directory (repro.launch.train)."""

import pytest

from repro.kernels import backend as kb
from repro.launch import train as T


@pytest.mark.parametrize("mesh_arg,groups,n,expected", [
    ("", None, 1, (1, 1, 1)),     # one chip: one group, not two
    ("", None, 4, (2, 2, 1)),
    ("", None, 8, (2, 4, 1)),
    ("", 4, 4, (4, 1, 1)),        # four groups, one per chip
    ("", 1, 4, (1, 4, 1)),
    ("4,1,1", None, 4, (4, 1, 1)),
    ("2,1,2", 7, 4, (2, 1, 2)),   # --mesh wins over --groups
])
def test_resolve_mesh_shape(mesh_arg, groups, n, expected):
    assert T.resolve_mesh_shape(mesh_arg, groups, n) == expected


@pytest.mark.parametrize("mesh_arg,groups,n,match", [
    ("", 2, 1, "must divide the 1 device"),
    ("", 3, 4, "must divide the 4 device"),
    ("2,2,1", None, 1, "spans 4 devices; this host has 1"),
    ("2,2", None, 4, "three sizes"),
])
def test_resolve_mesh_shape_says_why_it_does_not_fit(mesh_arg, groups, n,
                                                      match):
    with pytest.raises(ValueError, match=match):
        T.resolve_mesh_shape(mesh_arg, groups, n)


def test_launcher_rejects_two_groups_on_one_device(capsys):
    with pytest.raises(SystemExit):
        T.main(["--arch", "gpt2-small", "--reduced", "--groups", "2",
                "--steps", "1"])
    assert "must divide the 1 device" in capsys.readouterr().err


@pytest.mark.parametrize("backend,expected", [
    ("tpu-mosaic", True), ("interpret", False), ("jnp-ref", False),
    ("gpu-triton", False),
])
def test_pallas_follows_the_compiled_lane(backend, expected):
    kb.set_kernel_backend(backend)
    try:
        assert T.pallas_kernels_compiled() is expected
    finally:
        kb.set_kernel_backend(None)


def test_compile_cache_env_dir_wins(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(T.jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert T.use_compile_cache() == str(tmp_path)
    assert calls == []  # jax reads the variable itself; nothing else set


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    calls = []
    monkeypatch.setattr(T.jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = T.use_compile_cache()
    assert first == str(T.REPO_ROOT / ".jax_cache")
    assert T.use_compile_cache() == first  # no pid, time or temp name
    assert calls == [("jax_compilation_cache_dir", first)] * 2
