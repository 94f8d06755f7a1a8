"""The command refuses to measure anywhere but on a TPU."""
import json
import os
import shutil
import subprocess
import sys

from chipbench import spec

ARGS = ["--workload", "vitb-edp.search-rung", "--seed", "3",
        "--seconds", "1", "--trace", "0"]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env


def test_off_tpu_the_command_exits_nonzero_and_names_the_platform():
    p = subprocess.run([sys.executable, "-m", "chipbench.run", *ARGS],
                       cwd=spec.ROOT, env=_env(), capture_output=True,
                       text=True, timeout=240)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr
    assert not p.stdout.strip()


def test_without_the_program_the_command_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "-m", "chipbench.run", *ARGS],
                       cwd=tmp_path, env=_env(), capture_output=True,
                       text=True, timeout=240)
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result: {line}")
