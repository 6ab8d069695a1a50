"""The last line's keys, the rehearsal's verdict, and the bare checkout."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd, *args, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "cdtbench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("trace", [0, 1])
def test_cpu_rehearsal_exercises_every_phase_and_is_never_correct(trace):
    done = _run(ROOT, "--workload", "sdxl-base.solo30", "--seed",
                str(2**31 + 7), "--seconds", "3", "--trace", str(trace),
                "--rehearse")
    assert done.returncode == 1, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is False and line["failed"] == 0
    assert line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # no time is written under a metric's name from a CPU run
    assert line["metrics"] == {}


def test_without_a_tpu_there_is_no_result_line():
    done = _run(ROOT, "--workload", "sdxl-base.solo30", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert done.returncode == 3
    assert not [l for l in done.stdout.splitlines() if l.startswith("{")]


def test_a_checkout_with_only_the_benchmark_exits_non_zero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "cdtbench", tmp_path / "cdtbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = _run(tmp_path, "--workload", "sdxl-base.solo30", "--seed", "1",
                "--seconds", "1", "--trace", "0", timeout=120)
    assert done.returncode != 0
    assert not [l for l in done.stdout.splitlines() if l.startswith("{")]
