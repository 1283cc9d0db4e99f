"""A run refuses every platform but the TPU, and a checkout without the
program."""
import os
import shutil
import subprocess
import sys

from chipbench.tests.conftest import ROOT

ARGS = ["chipbench/run.py", "--workload", "q4-1chip", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_cpu_platform_exits_nonzero_with_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _fake_jax(kind, count=1, platform="tpu"):
    from types import SimpleNamespace as NS

    devs = [NS(platform=platform, device_kind=kind, id=i) for i in range(count)]
    return NS(devices=lambda: devs)


def test_device_checks():
    from chipbench.run import tpu_devices

    assert len(tpu_devices(_fake_jax("TPU v5 lite", 4), 1)) == 1
    assert len(tpu_devices(_fake_jax("TPU v5 lite", 4), 4)) == 4
    assert tpu_devices(_fake_jax("TPU v5 lite", 1), 4) is None  # too few chips
    assert tpu_devices(_fake_jax("TPU v9 unknown"), 1) is None  # no peaks for it
    assert tpu_devices(_fake_jax("cpu", platform="cpu"), 1) is None
