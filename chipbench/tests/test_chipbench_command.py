"""The command's refusal to run anywhere but on a TPU, or in a tree
that holds only the benchmark."""
import json
import os
import shutil
import subprocess
import sys

from chipbench import spec


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "smollm-135m.chat-c8", "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_a_host_without_a_tpu():
    p = _command(spec.ROOT)
    assert p.returncode != 0 and "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_command_refuses_a_tree_without_the_program(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
    json.loads((tmp_path / "BENCHMARK.json").read_text())
