"""CPU self-tests of the benchmark: four virtual devices, no chip.

    python -m pytest chipbench/tests

They sit outside the repository's ``testpaths``.  The environment is set
before JAX is first imported, which is why it is done here.
"""
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
