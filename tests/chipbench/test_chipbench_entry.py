"""The benchmark's command refuses a host without a TPU, and a checkout
that holds only the benchmark's own files, and prints no result."""
import os
import shutil
import subprocess
import sys

import chipbench_testkit as tk

ARGS = ["--workload", "vgg16-b5pool.poisson", "--seed", str(2 ** 31 + 3),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=240)


def test_run_exits_nonzero_on_cpu():
    p = _run(tk.REPO)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(tk.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(tk.REPO, "chipbench"),
                    os.path.join(tmp_path, "chipbench"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert not p.stdout.strip()
