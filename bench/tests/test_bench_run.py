"""``bench/run.py`` refuses to measure anywhere but on a chip in its
peaks table, and without the program beside it; it then prints no
result line and no device metric."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

ARGS = ["--workload", "fno_darcy.train", "--seed", "4294967301",
        "--seconds", "1", "--trace", "0"]


def _run(cwd: Path, run_py: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(run_py), *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and ("metrics" in obj or "correct" in obj):
            return False
    return True


def test_refuses_without_a_tpu():
    p = _run(ROOT, BENCH / "run.py")
    assert p.returncode == 3, p.stderr[-2000:]
    assert "no TPU" in p.stderr
    assert _no_result(p.stdout)


def test_refuses_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path, tmp_path / "bench" / "run.py")
    assert p.returncode != 0
    assert _no_result(p.stdout)


def test_device_not_in_peaks_table(monkeypatch):
    from harness import device

    class Dev:
        platform, device_kind = "tpu", "TPU v99"

    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(device.NoChip, match="not in bench/peaks.json"):
        device.check(1)
