"""bench/run.py finds no TPU here: it exits non-zero and prints no result."""
import json
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
ARGS = ["--workload", "esm2-650m.mlm", "--seed", str(2**31 + 1), "--seconds", "1"]


def no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def run_bench(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = run_bench(ROOT)
    assert p.returncode != 0
    assert no_result(p.stdout)
    assert "TPU chip" in p.stderr


def test_benchmark_files_alone_fail(tmp_path):
    """A directory with only BENCHMARK.json and bench/ has no program."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_bench(tmp_path)
    assert p.returncode != 0
    assert no_result(p.stdout)

