"""The device check and the peaks table: no chip, no result."""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from bench import device

ROOT = Path(__file__).resolve().parents[2]


class Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_v5e_peaks_are_the_published_ones():
    p = device.peaks_for("TPU v5 lite")
    assert p == {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                 "hbm_bytes": 16e9}
    assert "TPU v5e" in json.loads(device.PEAKS.read_text())["source"]


def test_unknown_device_kind_is_refused():
    with pytest.raises(device.NoAccelerator, match="no peaks"):
        device.accelerator(1, [Dev("tpu", "TPU v9 imaginary")])


def test_cpu_is_refused():
    with pytest.raises(device.NoAccelerator, match="no TPU"):
        device.accelerator(1, jax.devices())


def test_too_few_chips_are_refused():
    with pytest.raises(device.NoAccelerator, match="needs 4 chips"):
        device.accelerator(4, [Dev("tpu", "TPU v5 lite")])
    used, peaks = device.accelerator(1, [Dev("tpu", "TPU v5 lite")] * 4)
    assert len(used) == 1 and peaks["hbm_bytes_per_s"] == 819e9


def test_the_command_exits_nonzero_with_no_result_on_the_cpu():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-4b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
