"""The command as BENCHMARK.json names it: without a TPU it exits non-zero and
prints nothing on stdout, also in a directory that holds only
BENCHMARK.json and the benchmark's own files."""
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ARGS = ["--workload", "resnet20-offline", "--seed", str(2 ** 31 + 9),
        "--seconds", "1", "--trace", "0"]


def run_in(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_no_result():
    p = run_in(ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_in(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


@pytest.mark.parametrize("workload", ["resnet20-offline", "resnet8-offline",
                                      "resnet20-poisson"])
def test_cells_resolve(workload):
    from bench import run
    cell = run.load_cell(workload)
    assert cell.end_to_end and cell.per_layer
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    for m in cell.end_to_end + cell.per_layer:
        assert callable(run.load_reader(m["name"]))
