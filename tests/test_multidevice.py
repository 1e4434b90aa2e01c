"""Multi-device semantics via subprocesses (8 forced host devices).

Kept out-of-process so the main pytest run sees the single real CPU device
(per the assignment: no global XLA_FLAGS)."""

import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(__file__)
SRC = os.path.abspath(os.path.join(HERE, "..", "src"))

SCRIPTS = [
    "md_steps.py",
    "md_equivalence.py",
    "md_membership.py",
    "md_7b_dryrun.py",
    "md_dryrun_mini.py",
]


def _run(script):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "multidevice", script)],
        capture_output=True, text=True, timeout=900, env=env)
    if r.returncode != 0:
        raise AssertionError(
            f"{script} failed\n--- stdout ---\n{r.stdout[-3000:]}"
            f"\n--- stderr ---\n{r.stderr[-3000:]}")
    return r.stdout


@pytest.mark.slow
@pytest.mark.parametrize("script", SCRIPTS)
def test_multidevice(script):
    out = _run(script)
    assert "OK" in out
