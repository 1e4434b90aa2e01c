"""Tests of the benchmark's own code, on the CPU: ``PYTHONPATH=src python -m
pytest bench/tests``. Four host devices stand in for a four-chip mesh."""

import os
import sys
import tempfile
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
# CPU programs stay out of the checkout's cache, which a chip run fills.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      str(Path(tempfile.gettempdir()) / "bench-tests-cache"))
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
