"""No CPU path: a run without a TPU exits non-zero and prints no result."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from chipbench import device

ROOT = pathlib.Path(__file__).resolve().parents[2]


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


class _Jax:
    def __init__(self, devs):
        self._devs = devs

    def devices(self):
        return self._devs


def test_peaks_are_keyed_by_device_kind_with_a_source():
    peaks = device.PEAKS["TPU v5 lite"]
    assert peaks["bf16_flops"] == 197e12 and peaks["int8_ops"] == 393e12
    assert peaks["hbm_bytes_per_s"] == 819e9 and peaks["hbm_bytes"] == 16e9
    assert "TPU v5e" in peaks["source"]


@pytest.mark.parametrize("devs,chips", [
    ([_Dev("cpu", "cpu")], 1),                       # no TPU
    ([_Dev("tpu", "TPU v5 lite")], 4),               # too few chips
    ([_Dev("tpu", "TPU v9 imaginary")], 1),          # no published peaks
])
def test_require_refuses(devs, chips):
    with pytest.raises(device.NoAccelerator):
        device.require(_Jax(devs), chips)


def test_require_returns_devices_and_peaks():
    devs = [_Dev("tpu", "TPU v5 lite")] * 4
    got, peaks = device.require(_Jax(devs), 1)
    assert got == devs[:1] and peaks is device.PEAKS["TPU v5 lite"]


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "geoglue-bf16.serve", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert "correct" not in obj


def test_cpu_run_exits_nonzero_without_result():
    _no_result(_run(ROOT))


def test_run_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run(tmp_path))
