"""The command refuses to measure anything but the chips a cell needs."""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, REPO


def test_no_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", "smollm-chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_without_the_program_it_fails(tmp_path):
    """In a directory holding only BENCHMARK.json and bench/, the run
    exits non-zero and prints no result."""
    import shutil
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "smollm-chat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, capture_output=True,
                       text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("devs,needs", [
    ([_Dev("tpu", "TPU v9 imaginary")], 1),        # not in the peak table
    ([_Dev("tpu", "TPU v5 lite")], 4),             # too few chips
    ([_Dev("cpu", "cpu")], 1),                     # no TPU
])
def test_wrong_devices_exit_2(monkeypatch, devs, needs):
    import jax
    sys.path.insert(0, BENCH)
    import run
    monkeypatch.setattr(jax, "devices", lambda: devs)
    with pytest.raises(SystemExit) as e:
        run.chips_or_exit(needs, REPO)
    assert e.value.code == 2


def test_peak_table_knows_v5e_and_nothing_else_silently():
    from harness.load import peaks
    p = peaks("TPU v5 lite", REPO)
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v4", REPO)
    table = json.load(open(os.path.join(BENCH, "peaks.json")))
    assert "Google Cloud" in table["source"]
