"""The command refuses to measure without a TPU, and without the program."""

import os
import shutil
import subprocess
import sys

from bench import common, run

ARGS = ["--workload", "deep-f32.build", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def test_refuses_a_cpu_device(capsys):
    assert run.main(ARGS) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "TPU" in out.err


def test_refuses_without_the_program(tmp_path):
    shutil.copy(common.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(common.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
