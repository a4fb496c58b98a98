"""The cells' programs compiled for a described ``v5e:2x2`` chip at their
real sizes, with no chip attached: XLA and Mosaic refuse here what they
would refuse there (memory, tiling), and ``memory_analysis()`` says what one
program needs.  No time and no result comes from this; the chip gives those.

Only one process may hold libtpu, so the topology is described inside a
fixture of this one file, never at import.
"""

import json
import os

import pytest

from benchmark import harness, offchip

HBM = 16.9e9        # bytes_limit a TPU v5 lite reports (my chip run, PR 23)


@pytest.fixture(scope="module")
def device():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    # a described device's executable cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield offchip.topology_device()
    except Exception as exc:                       # no libtpu, lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


@pytest.fixture(scope="module")
def train_step(device):
    cell = harness.resolve_cell("gpt2m-train-1chip")
    return cell, offchip.compile_train_step(cell, device)


@pytest.fixture(scope="module")
def serve_cell():
    return harness.resolve_cell("mistral7b-serve-offline")


def test_train_step_fits_and_holds_the_three_flash_kernels(train_step):
    cell, compiled = train_step
    mem = offchip.memory(compiled)
    # f32 weights + Adam's two moments, donated: 12 bytes a parameter
    assert 4.0e9 < mem["argument_size_in_bytes"] < 4.6e9
    assert mem["alias_size_in_bytes"] > 0.95 * mem["argument_size_in_bytes"]
    assert mem["total_bytes"] < HBM
    # over the floor of a quarter of the chip's memory by a wide margin
    assert mem["total_bytes"] > 0.5 * 16e9
    # forward, dq and dkv in each of the 24 layers
    assert compiled.as_text().count("tpu_custom_call") == 3 * cell.arch["layers"]


def test_spec_round_fits(device, serve_cell):
    mem = offchip.memory(offchip.compile_spec_round(serve_cell, device))
    assert mem["total_bytes"] < HBM
    # bf16 weights (target + draft) and both caches: about 9.4 GB
    assert 8.5e9 < mem["argument_size_in_bytes"] < 10.5e9


def test_spec_admit_of_the_longest_prompt_fits(device, serve_cell):
    longest = max(serve_cell.traffic["prompt_ladder"])
    mem = offchip.memory(
        offchip.compile_spec_admit(serve_cell, device, longest))
    assert mem["total_bytes"] < HBM


def test_both_serving_cells_share_configuration_and_lengths():
    a = harness.resolve_cell("mistral7b-serve-offline")
    b = harness.resolve_cell("mistral7b-serve-chat")
    assert a.config == b.config
    for key in ("prompt_ladder", "prompt_lognormal", "output_lognormal",
                "max_total", "cycle"):
        assert a.traffic[key] == b.traffic[key], key
    assert (a.kind, b.kind) == ("closed", "open")
    assert b.traffic["rate_per_s"] == pytest.approx(
        0.8 * b.traffic["knee_per_s"], rel=0.03)
    json.dumps(a.traffic)
