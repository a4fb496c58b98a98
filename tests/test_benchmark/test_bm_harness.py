"""The harness end to end on the CPU at a tiny size, below its device gate:
counts and verdicts, never times.  The toy benchmark under ``toy/`` is made
of new files alone (a manifest, configurations, traffic mixes, limits, a
per-layer metric with a reader of its own): adding a cell edits nothing.

The faults: with the timed path broken underneath, ``correct`` has to come
out false; with a control in the program's place, the comparison fails it.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import harness

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.normpath(os.path.join(HERE, "..", ".."))
TOY = os.path.join(HERE, "toy")


def toy_cell(name):
    manifest = harness.load_json(os.path.join(TOY, "BENCHMARK.json"))
    return harness.resolve_cell(name, manifest, bench_dir=TOY)


def run(name, seed, trace=False, seconds=0.5):
    result = harness.run_cell(toy_cell(name), seed, seconds, trace)
    json.dumps(result)                      # the line must serialise
    return result


# -- the rehearsal -----------------------------------------------------------


@pytest.mark.parametrize("name,metric", [
    ("toy-train", "train_tokens_per_s"),
    ("toy-closed", "serve_tokens_per_s"),
    ("toy-open", "tpot_p80_ms"),
])
def test_rehearsal_prints_a_correct_result_line(name, metric):
    result = run(name, seed=2 ** 31 + 17)
    assert list(result)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {metric, "setup_s"}
    for m in result["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert result["device"]["platform"] == "cpu"      # named, never a TPU
    for pair in result["compared"].values():
        assert pair["value"] <= pair["limit"]


def test_traced_rehearsal_reads_per_layer_metrics_through_toy_readers():
    result = run("toy-train", seed=5, trace=True)
    assert result["correct"] is True
    # the toy's own reader and a reader of the benchmark's, both by name;
    # the idle share finds no device plane on a CPU and is left out
    assert set(result["metrics"]) == {"toy_steps", "toy_compiles"}
    assert result["metrics"]["toy_steps"]["value"] == result["attempted"]
    assert result["metrics"]["toy_compiles"]["value"] == 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert result["device"]["window_s"] > 0


def test_same_seed_same_numbers_compared():
    a, b = run("toy-train", seed=11), run("toy-train", seed=11)
    assert a["compared"] == b["compared"]
    c = run("toy-train", seed=12)
    assert a["compared"] != c["compared"]


# -- refusals ----------------------------------------------------------------


def test_the_command_refuses_the_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "gpt2m-train-1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_require_chips_refuses_a_non_tpu_and_an_unknown_kind(monkeypatch):
    import jax

    cell = toy_cell("toy-train")
    with pytest.raises(harness.BenchmarkError, match="needs a TPU"):
        harness.require_chips(cell)

    class Fake:
        platform, device_kind = "tpu", "TPU v9 mega"

        def memory_stats(self):
            return {}

    monkeypatch.setattr(jax, "devices", lambda *a: [Fake()])
    with pytest.raises(harness.BenchmarkError, match="peaks.json"):
        harness.require_chips(cell)
    Fake.device_kind = "TPU v5 lite"
    assert harness.require_chips(cell)["kind"] == "TPU v5 lite"
    four = toy_cell("toy-train")
    four.chips = 4
    with pytest.raises(harness.BenchmarkError, match="needs 4 chip"):
        harness.require_chips(four)


def test_the_command_refuses_a_directory_without_the_program(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "gpt2m-train-1chip", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


# -- faults planted under the timed path -------------------------------------


def _wrap_train_step(monkeypatch, wrap):
    import rocket_tpu.core.module as module

    original = module.build_train_step

    def patched(*args, **kw):
        steps = dict(original(*args, **kw))
        steps["sync"] = wrap(steps["sync"])
        return steps

    monkeypatch.setattr(module, "build_train_step", patched)


TRAIN_CELLS = ["toy-train", "mix-train"]      # one of each architecture
CLOSED_CELLS = ["toy-closed", "mix-closed"]


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_fault_state_returned_unchanged_is_not_correct(monkeypatch, name):
    def wrap(step):
        def broken(state, batch, *rest):
            _, logs = step(state, batch, *rest)
            return state, logs
        return broken

    _wrap_train_step(monkeypatch, wrap)
    result = run(name, seed=21)
    assert result["correct"] is False
    over = {k for k, p in result["compared"].items()
            if p["value"] > p["limit"]}
    # no gradient reached the optimizer, no parameter moved: both read 1
    assert {"grad_gap", "change_gap"} <= over
    assert result["compared"]["grad_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_fault_half_of_the_batch_left_out_is_not_correct(monkeypatch, name):
    import jax

    def wrap(step):
        def broken(state, batch, *rest):
            half = jax.tree_util.tree_map(
                lambda x: x[: x.shape[0] // 2], batch)
            return step(state, half, *rest)
        return broken

    _wrap_train_step(monkeypatch, wrap)
    result = run(name, seed=22)
    assert result["correct"] is False


@pytest.mark.parametrize("name", CLOSED_CELLS)
def test_fault_an_altered_token_is_not_correct(monkeypatch, name):
    from rocket_tpu.models.generate import ContinuousBatcher

    original = ContinuousBatcher.step

    def broken(self):
        import jax.numpy as jnp

        out = original(self)
        buf, n_tok = self.state[0], self.state[1]
        rows = jnp.arange(buf.shape[0])
        last = buf[rows, n_tok - 1]
        self.state = (buf.at[rows, n_tok - 1].set((last + 1) % 250),
                      ) + tuple(self.state[1:])
        return out

    monkeypatch.setattr(ContinuousBatcher, "step", broken)
    result = run(name, seed=23)
    assert result["correct"] is False
    assert result["compared"]["served_gap"]["value"] > 1.0


# -- controls: the reference in the program's place, a precision lower -------


@pytest.mark.parametrize("name", CLOSED_CELLS)
def test_control_served_tokens_of_a_lower_precision_fail_the_limit(name):
    from benchmark.kinds import serving

    cell = toy_cell(name)
    limit = harness.load_json(os.path.join(
        TOY, "limits", name + ".json"))["served_gap"]
    rng = np.random.default_rng(0)
    sample = []
    for _ in range(4):
        prompt = rng.integers(0, 256, 16).astype(np.int32)
        sample.append({"prompt": prompt, "tokens": np.concatenate(
            [prompt, rng.integers(0, 256, 24).astype(np.int32)])})
    exact = serving.served_gaps(cell, 5, sample, altered="f32")
    assert exact["served_gap"] == 0.0 and exact["tokens_compared"] == 96
    random_tokens = serving.served_gaps(cell, 5, sample)
    assert random_tokens["served_gap"] > limit
    low = serving.served_gaps(cell, 5, sample, altered="fp8")
    assert low["served_gap"] > 0.0


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_control_training_in_a_lower_precision_reads_wider_than_the_program(
        name):
    from benchmark.kinds import train
    from benchmark import traffic

    cell = toy_cell(name)
    decoder = cell.family.reference
    opt = cell.traffic["optimizer"]
    tokens = traffic.markov_tokens(12, 32, cell.arch["vocab"], 3)
    batches = [tokens[0:4], tokens[4:8], tokens[8:12]]
    params = train.reference_params(cell, 3)
    ref = decoder.train_steps(cell.arch, opt, params, batches)
    same = train.gaps(ref, ref)
    assert same == {"loss_gap": 0.0, "grad_gap": 0.0, "change_gap": 0.0}
    fp8 = train.gaps(decoder.train_steps(cell.arch, opt, params, batches,
                                         prec="fp8"), ref)
    bf16 = train.gaps(decoder.train_steps(cell.arch, opt, params, batches,
                                          prec="bf16"), ref)
    assert fp8["grad_gap"] > 3 * bf16["grad_gap"] > 0
    half = train.gaps(decoder.train_steps(cell.arch, opt, params, batches,
                                          rows=range(2)), ref)
    assert half["grad_gap"] > 3 * bf16["grad_gap"]
    frozen = train.gaps(decoder.train_steps(cell.arch, opt, params, batches,
                                            skip_update=True), ref)
    assert frozen["change_gap"] == pytest.approx(1.0)
