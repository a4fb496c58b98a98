"""Test configuration: force an 8-fake-device CPU backend.

SURVEY §4: multi-device behavior is tested without a cluster via
``--xla_force_host_platform_device_count=8`` — the TPU-world equivalent of a
fake backend.  Must run before the first ``import jax`` in any test module.
"""

import os
import tempfile

# Tests run on the CPU; the chip belongs to chip_smoke.py.
os.environ["JAX_PLATFORMS"] = "cpu"

# Hermetic warm-start tier: tests place the compile cache from outside,
# as any launcher of the program does — a fresh per-session tmp dir, never
# the repo's shared experiments/compile_cache/.  A populated shared cache
# changes what LATER sessions' compiles return (a cache-retrieved
# executable reports alias_size_in_bytes=0 in memory_analysis(), breaking
# the donation guards in test_ladder_shapes.py) and would make tier-1
# results depend on who ran before.  Tests that probe dir resolution
# override this env var themselves.
os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
    prefix="rocket_tpu_test_compile_cache_")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests (multi-process rendezvous)"
    )
    config.addinivalue_line(
        "markers",
        "resilience: fault-tolerance / chaos tests (see docs/reliability.md; "
        "long sweeps run with -m 'slow and resilience')",
    )
    config.addinivalue_line(
        "markers",
        "serving: serving-robustness tests (rocket_tpu.serve — deadlines, "
        "backpressure, watchdog recovery; see docs/reliability.md)",
    )
    config.addinivalue_line(
        "markers",
        "tracing: structured-tracing / flight-recorder tests "
        "(rocket_tpu.observe.trace|recorder; see docs/observability.md)",
    )
    config.addinivalue_line(
        "markers",
        "fleet: multi-replica serving fleet tests (rocket_tpu.serve "
        "router/fleet — routing, lane handoff, replica self-healing; "
        "see docs/reliability.md; the thousand-request trace is slow)",
    )
    config.addinivalue_line(
        "markers",
        "elastic: elastic-restore / preemption-persistence tests "
        "(mesh-stamped manifests, reshard-on-restore, emergency tier; "
        "see docs/reliability.md)",
    )
    config.addinivalue_line(
        "markers",
        "goodput: goodput-ledger / retrace-sentinel / metrics-export tests "
        "(rocket_tpu.observe.ledger|export; see docs/observability.md "
        "\"Goodput & metrics export\")",
    )
    config.addinivalue_line(
        "markers",
        "kvcache: prefix-cache tier tests (rocket_tpu.serve.kvstore — "
        "page hashing, LRU eviction, cached-prefix bit-equality, session "
        "affinity)",
    )
    config.addinivalue_line(
        "markers",
        "procfleet: process-backed fleet tests (rocket_tpu.serve "
        "procfleet/wire/worker/autoscale — wire protocol, worker "
        "subprocess, kill -9 salvage, goodput-driven autoscaling; see "
        "docs/reliability.md \"Process fleet & autoscaling\"; the "
        "full kill-mid-burst and autoscale bursts are slow)",
    )
    config.addinivalue_line(
        "markers",
        "kvpool: fleet KV page-tier tests (rocket_tpu.serve.kvpool — "
        "binary page codec, pool push/fetch/NACK, cross-process page "
        "transfer, disaggregated prefill; spawn-heavy cases live in "
        "tests/test_kvpool_proc.py)",
    )
    config.addinivalue_line(
        "markers",
        "trainserve: train-while-serve tests (rocket_tpu.persist.publish "
        "/ rocket_tpu.serve feed|loop swap path — verified publication, "
        "live hot-swap, rejected torn publish, bounded rollback, "
        "kill-mid-swap heal; see docs/reliability.md \"Live weight "
        "updates\")",
    )
    config.addinivalue_line(
        "markers",
        "tenants: multi-tenant serving tests (rocket_tpu.serve "
        "queue/loop, driven by serve/loadgen's seeded traces — SLO "
        "classes, weighted-fair admission, batch preemption with "
        "bit-equal resume; see docs/reliability.md \"Multi-tenant "
        "serving\"; spawn-heavy cases live in tests/test_tenants_proc.py)",
    )
    config.addinivalue_line(
        "markers",
        "warmstart: warm-start tests (rocket_tpu.tune.compile_cache and "
        "rocket_tpu.tune.warmup, all the package holds — persistent "
        "compile cache, AOT executable reuse, pre-warmed/standby spawns)",
    )


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 fake cpu devices, got {devs}"
    return devs


@pytest.fixture
def decode_kernel_here(monkeypatch):
    """``ops.decode_attention`` with its refusal of a backend that is no TPU
    taken out: a call the kernel would take on the chip takes it here, in
    interpret mode.  (Patching ``_on_tpu`` instead would ask for Mosaic.)"""
    from rocket_tpu.ops import decode_attention

    real = decode_attention.why_not

    def why_not(q, k_cache, **kw):
        reason = real(q, k_cache, **kw)
        return None if reason == "backend" else reason

    monkeypatch.setattr(decode_attention, "why_not", why_not)

