"""The peak table and the refusal to measure without a TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import peaks

ROOT = Path(__file__).resolve().parents[2]


class Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_v5e_peaks():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_unknown_kind_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v99")
    with pytest.raises(peaks.UnknownDevice):
        peaks.require_accelerator([Dev("tpu", "TPU v99")], 1)


def test_no_tpu_or_too_few_chips_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.require_accelerator([Dev("cpu", "cpu")], 1)
    with pytest.raises(peaks.UnknownDevice):
        peaks.require_accelerator([], 1)
    with pytest.raises(peaks.UnknownDevice):
        peaks.require_accelerator([Dev("tpu", "TPU v5 lite")], 4)
    assert peaks.require_accelerator([Dev("tpu", "TPU v5 lite")] * 4, 4)


def test_run_exits_nonzero_without_a_tpu():
    cell = json.loads((ROOT / "BENCHMARK.json").read_text())[
        "workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", cell, "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr
