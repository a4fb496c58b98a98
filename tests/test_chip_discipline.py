"""The rules that keep a run honest about the chip (ISSUE 21).

- ``chip_smoke.py`` refuses anything but a TPU: on the CPU it exits
  non-zero at once, names the platform it found and prints no result —
  also when it is the only file of the repo in its directory;
- a chip belongs to one process: ``chip_smoke.py`` touches JAX itself,
  so it starts no process beside it.

(An unknown device kind has no peak: the benchmark's own table,
``benchmark/peaks.json``, and its refusal are ``tests/test_benchmark/``'s.)
"""

import ast
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)



# -- chip_smoke.py refuses the CPU -------------------------------------------


def _run_smoke(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )


def test_chip_smoke_refuses_cpu_and_names_the_platform():
    proc = _run_smoke(REPO)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "no phase ran" in proc.stderr
    # no result: nothing on stdout a driver could take for one
    assert proc.stdout.strip() == ""


def test_chip_smoke_alone_in_a_directory_fails_without_a_result(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run_smoke(str(tmp_path))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_chip_smoke_imports_nothing_of_the_repo_before_the_device_check():
    # alone on a machine WITH a chip it must fail too: everything it does
    # after the device check needs the package beside it
    with open(os.path.join(REPO, "chip_smoke.py")) as fh:
        tree = ast.parse(fh.read())
    top_level = {
        alias.name.split(".")[0]
        for node in tree.body if isinstance(node, (ast.Import, ast.ImportFrom))
        for alias in (node.names if isinstance(node, ast.Import)
                      else [ast.alias(node.module or "")])
    }
    assert "rocket_tpu" not in top_level
    assert "jax" in top_level


# -- a chip belongs to one process --------------------------------------------


@pytest.mark.parametrize("program", ["chip_smoke.py"])
def test_chip_holding_programs_spawn_no_child(program):
    # chip_smoke.py touches JAX itself, so it holds the chip: it may not
    # start a process (a probe, a worker) beside it
    with open(os.path.join(REPO, program)) as fh:
        source = fh.read()
    imported = {
        (alias.name if isinstance(node, ast.Import) else node.module or "")
        .split(".")[0]
        for node in ast.walk(ast.parse(source))
        if isinstance(node, (ast.Import, ast.ImportFrom))
        for alias in node.names
    }
    assert not imported & {"subprocess", "multiprocessing"}, imported
    assert "ProcReplica" not in source and "os.fork" not in source
