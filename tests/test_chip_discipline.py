"""The rules that keep a run honest about the chip (ISSUE 21).

- ``chip_smoke.py`` refuses anything but a TPU: on the CPU it exits
  non-zero at once, names the platform it found and prints no result —
  also when it is the only file of the repo in its directory;
- an unknown device kind has no peak: the cost model raises and names it,
  and the live MFU/MBU gauges emit nothing;
- a chip belongs to one process: the tune search's parent, whose probe
  children need the chip, never initialises a JAX backend itself.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from rocket_tpu.tune import cost_model  # noqa: E402
from rocket_tpu.tune import search  # noqa: E402
from rocket_tpu.tune.space import TuneParam, TuneSpace  # noqa: E402


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
    import ast

    with open(os.path.join(REPO, "chip_smoke.py")) as fh:
        tree = ast.parse(fh.read())
    top_level = {
        alias.name.split(".")[0]
        for node in tree.body if isinstance(node, (ast.Import, ast.ImportFrom))
        for alias in (node.names if isinstance(node, ast.Import)
                      else [ast.alias(node.module or "")])
    }
    assert "rocket_tpu" not in top_level and "bench" not in top_level
    assert "jax" in top_level


# -- no peak for a kind the table does not hold ------------------------------


@pytest.mark.parametrize("peak", [cost_model.device_peak_flops,
                                  cost_model.device_peak_hbm_bytes])
@pytest.mark.parametrize("kind", ["cpu", "TPU v9 hyper"])
def test_unknown_device_kind_raises_and_names_it(peak, kind):
    with pytest.raises(ValueError, match=kind):
        peak(kind)


def test_local_cpu_device_has_no_peak(devices):
    # device_kind=None asks the local device — a CPU here
    with pytest.raises(ValueError, match="cpu"):
        cost_model.device_peak_flops()


# -- the tune search's parent stays off the chip ------------------------------


def _no_backend(monkeypatch):
    import jax

    def touched(*args, **kwargs):
        raise AssertionError("the search parent initialised a JAX backend")

    for name in ("devices", "local_devices", "default_backend",
                 "device_count"):
        monkeypatch.setattr(jax, name, touched)


def test_search_with_stub_probe_never_touches_jax_in_the_parent(
        monkeypatch, tmp_path):
    monkeypatch.setenv("ROCKET_TPU_TUNE_DIR", str(tmp_path / "tunes"))
    _no_backend(monkeypatch)
    asked = []

    def identity_child():
        asked.append(1)
        return {"device": "TPU v5 lite", "backend": "tpu"}

    monkeypatch.setattr(search, "device_identity", identity_child)
    space = TuneSpace((TuneParam("p", ({"batch": 8}, {"batch": 16})),))
    record = search.autotune(
        space=space, seed_k=2, rung_steps=(2,), save=True,
        probe=lambda tune, *a: {"value": 100.0 * tune["batch"]},
        log=lambda s: None,
    )
    # one identity child for the whole search, stamped on the record and
    # used for the roofline seeding — no jax.devices() anywhere
    assert asked == [1]
    assert record["device"] == "TPU v5 lite" and record["backend"] == "tpu"
    assert record["tune"]["batch"] == 16 and record["probes"] == 2
    # the zero re-search contract holds off-backend too
    again = search.autotune(space=space)
    assert again["probes"] == 0 and again["reused"] is True


@pytest.mark.parametrize("program", ["bench.py", "chip_smoke.py"])
def test_chip_holding_programs_spawn_no_child(program):
    # bench.py and chip_smoke.py touch JAX themselves, so they hold the
    # chip: neither may start a process (a probe, a worker) beside it
    import ast

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


def test_device_identity_asks_a_child_process(monkeypatch):
    _no_backend(monkeypatch)
    ident = search.device_identity()  # the child inherits JAX_PLATFORMS=cpu
    assert ident == {"device": "cpu", "backend": "cpu"}
    json.dumps(ident)  # plain data: it is stamped into the tune record
