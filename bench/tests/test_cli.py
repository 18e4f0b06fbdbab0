"""The command line: no result without a TPU, none from a lone copy of the
benchmark, and no TPU library loaded by importing a module."""

import json
import os
import shutil
import subprocess
import sys

import registry

ARGS = ["--workload", "ungm-alg6-n2e20-b32.stream", "--seed", str(2**33 + 1),
        "--seconds", "1", "--trace", "0"]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def _results(stdout):
    out = []
    for line in stdout.splitlines():
        try:
            out.append(json.loads(line))
        except ValueError:
            pass
    return out


def test_no_tpu_no_result():
    p = subprocess.run([sys.executable, str(registry.ROOT / "bench/run.py"), *ARGS],
                       capture_output=True, text=True, env=_env(), timeout=120)
    assert p.returncode != 0
    assert _results(p.stdout) == []
    assert "needs 1 TPU" in p.stderr


def test_lone_copy_no_result(tmp_path):
    shutil.copy(registry.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(registry.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=tmp_path,
                       capture_output=True, text=True, env=_env(), timeout=120)
    assert p.returncode != 0
    assert _results(p.stdout) == []


def test_importing_loads_no_backend():
    code = ("import sys; sys.path[:0] = [%r, %r]\n"
            "import run, registry, check, loadgen, system, tracing, faults, calibrate\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n"
            % (str(registry.ROOT / "bench"), str(registry.ROOT / "src")))
    env = _env()
    env.pop("JAX_PLATFORMS")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       env=env, timeout=120)
    assert p.returncode == 0, p.stderr
