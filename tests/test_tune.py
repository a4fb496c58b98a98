"""rocket_tpu.tune — the search-driven autotuner (cost model, tune
space, persistent store, successive halving) and its reach into bench.py
(`_resolve_gpt2_tune` precedence) and the runtime donate default.

The CPU-proxy smoke at the bottom runs the REAL subprocess probe path
(`bench_probe` → fresh `python -c` → `bench.bench_gpt2(tune=...)`) over
the tiny 2-point space — the zero-re-search contract (second `autotune`
call returns the stored record with ``probes == 0``) is the acceptance
bar from the ISSUE.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from rocket_tpu.tune import (  # noqa: E402
    TuneParam,
    TuneSpace,
    autotune,
    best_tune,
    canonical_tune_key,
    device_peak_flops,
    device_peak_hbm_bytes,
    gpt2_space,
    gpt2_step_flops,
    predict_point,
    runtime_default,
    save_tune,
    successive_halving,
)


@pytest.fixture()
def tune_dir(tmp_path, monkeypatch):
    d = tmp_path / "tunes"
    monkeypatch.setenv("ROCKET_TPU_TUNE_DIR", str(d))
    return d


# -- cost model ---------------------------------------------------------


V5E = "TPU v5 lite"  # the chip the CPU-proxy tests model


def test_peaks_keyed_by_kind(devices):
    assert device_peak_flops(V5E) == 197e12
    assert device_peak_hbm_bytes(V5E) == 819e9
    assert device_peak_flops("TPU v4") != device_peak_flops(V5E)


def test_cost_model_orderings(devices):
    """The roofline must rank knobs the way the measured ladder does:
    remat taxes FLOPs, fused_ce deletes the logits round-trip bytes,
    donate=False pays a params copy."""
    base = {"batch": 8, "seq": 1024}
    p = predict_point(base, V5E)
    assert p["flops"] > 0 and p["bytes"] > 0 and p["seconds"] > 0
    assert predict_point({**base, "remat": True}, V5E)["flops"] > p["flops"]
    assert (predict_point({**base, "fused_ce": True}, V5E)["bytes"]
            < p["bytes"])
    assert (predict_point({**base, "donate": False}, V5E)["bytes"]
            > p["bytes"])
    assert (predict_point({**base, "mu_dtype": "bf16"}, V5E)["bytes"]
            < p["bytes"])


def test_gpt2_step_flops_is_benchs(devices):
    """bench.py re-exports the tune package's FLOPs accounting — one
    definition, two consumers (ladder MFU and search seeding)."""
    import bench

    assert bench.gpt2_step_flops is gpt2_step_flops


# -- space --------------------------------------------------------------


def test_space_candidates_merge_fragments(devices):
    sp = TuneSpace((
        TuneParam("a", ({"x": 1}, {"x": 2})),
        TuneParam("b", ({}, {"y": True})),
    ))
    cands = list(sp.candidates())
    assert sp.size == 4 and len(cands) == 4
    assert {"x": 2, "y": True} in cands


def test_space_advisory_keys_stripped_from_bench_tune(devices):
    sp = gpt2_space()
    advisory = sp.advisory_keys()
    assert "prefetch" in advisory and "mesh" in advisory
    point = {"batch": 8, "prefetch": 2, "mesh": "fsdp"}
    bench_point = sp.bench_tune(point)
    assert bench_point == {"batch": 8}


def test_canonical_key_resolves_default_blocks(devices):
    """An explicit block pair equal to auto_blocks(seq) must collide
    with the library-default point — the sweep dedupe contract."""
    from rocket_tpu.ops.flash import auto_blocks

    bq, bk = auto_blocks(1024)
    defaults = {"seq": 1024, "block_q": None, "block_k": None}
    explicit = canonical_tune_key(
        {"block_q": bq, "block_k": bk}, defaults=defaults
    )
    implied = canonical_tune_key({}, defaults=defaults)
    assert explicit == implied
    other = canonical_tune_key({"block_q": bq // 2}, defaults=defaults)
    assert other != implied


# -- store --------------------------------------------------------------


def _record(**kw):
    import jax

    rec = {
        "model": "gpt2",
        "device": jax.devices()[0].device_kind,
        "backend": jax.default_backend(),
        "batch": 8,
        "tune": {"batch": 8},
        "value": 100.0,
    }
    rec.update(kw)
    return rec


def test_store_round_trip_and_matching(devices, tune_dir):
    save_tune(_record(value=100.0))
    hit = best_tune(model="gpt2")
    assert hit is not None and hit["value"] == 100.0
    assert hit["schema"] == 1 and "created" in hit
    # wrong silicon / backend must NOT match
    assert best_tune(model="gpt2", device="TPU v999") is None
    assert best_tune(model="gpt2", backend="not-a-backend") is None
    # newer record for the same key wins
    save_tune(_record(value=200.0, tune={"batch": 8, "donate": False}))
    assert best_tune(model="gpt2")["value"] == 200.0


def test_store_batch_specific_beats_wildcard(devices, tune_dir):
    save_tune(_record(batch=8, value=1.0))
    save_tune(_record(batch=16, value=2.0, tune={"batch": 16}))
    assert best_tune(model="gpt2", batch=16)["value"] == 2.0
    assert best_tune(model="gpt2", batch=8)["value"] == 1.0


def test_runtime_default_consults_store(devices, tune_dir):
    # no record: caller default
    assert runtime_default("donate", default=True) is True
    save_tune(_record(tune={"batch": 8, "donate": False}))
    assert runtime_default("donate", default=True) is False
    # knob absent from the record: caller default again
    assert runtime_default("prefetch", default=3) == 3


def test_save_tune_rejects_incomplete(devices, tune_dir):
    with pytest.raises(ValueError):
        save_tune({"model": "gpt2"})


def test_engine_donate_none_consults_store(devices, tune_dir):
    from rocket_tpu.engine.step import _resolve_donate

    assert _resolve_donate(None) is True       # no record -> historical
    assert _resolve_donate(False) is False     # explicit wins, no lookup
    save_tune(_record(tune={"batch": 8, "donate": False}))
    assert _resolve_donate(None) is False


# -- bench precedence ---------------------------------------------------


def test_resolve_gpt2_tune_precedence(devices, tune_dir, monkeypatch):
    """defaults < store < BENCH_GPT2_TUNE < explicit tune=."""
    import bench

    monkeypatch.delenv("BENCH_GPT2_TUNE", raising=False)
    monkeypatch.delenv("BENCH_NO_TUNE_STORE", raising=False)
    save_tune(_record(tune={"batch": 8, "hidden": 64}))

    merged, survived = bench._resolve_gpt2_tune(None)
    assert merged["hidden"] == 64 and "hidden" in survived

    monkeypatch.setenv("BENCH_GPT2_TUNE", json.dumps({"hidden": 32}))
    merged, survived = bench._resolve_gpt2_tune(None)
    assert merged["hidden"] == 32 and "hidden" not in survived

    merged, _ = bench._resolve_gpt2_tune({"hidden": 16})
    assert merged["hidden"] == 16

    monkeypatch.setenv("BENCH_NO_TUNE_STORE", "1")
    monkeypatch.delenv("BENCH_GPT2_TUNE")
    merged, survived = bench._resolve_gpt2_tune(None)
    assert merged["hidden"] == 768 and not survived


# -- successive halving (fake probe: deterministic, no subprocesses) ----

CPU_IDENTITY = {"device": "cpu", "backend": "cpu"}


def test_successive_halving_seeds_and_halves(devices, tune_dir):
    space = TuneSpace((
        TuneParam("p", tuple({"batch": b} for b in (1, 2, 3, 4))),
    ))
    calls = []

    def fake_probe(tune, steps, warmup, timeout_s):
        calls.append((dict(tune), steps))
        return {"value": 1000.0 * tune["batch"], "mfu": 0.1}

    rec = successive_halving(
        space, base={"seq": 64}, seed_k=4, eta=2, rung_steps=(2, 5),
        probe=fake_probe, save=True, log=lambda s: None,
        identity=CPU_IDENTITY, device_kind=V5E,
    )
    # rung 0 probes all 4 seeds at 2 steps, keeps ceil(4/2)=2;
    # rung 1 (last) probes 2 at 5 steps, keeps 1
    assert [s for _, s in calls] == [2, 2, 2, 2, 5, 5]
    assert rec["probes"] == 6
    assert rec["tune"]["batch"] == 4 and rec["value"] == 4000.0
    assert rec["tune"]["seq"] == 64  # base pinned through
    assert len(rec["rungs"]) == 2
    # persisted: best_tune round-trips it
    assert best_tune(model="gpt2")["value"] == 4000.0


def test_successive_halving_drops_dead_points(devices, tune_dir):
    space = TuneSpace((
        TuneParam("p", tuple({"batch": b} for b in (1, 2, 3))),
    ))

    def fake_probe(tune, steps, warmup, timeout_s):
        if tune["batch"] == 3:  # the best-predicted point dies
            return {"value": None, "error": "boom"}
        return {"value": 1000.0 * tune["batch"]}

    rec = successive_halving(
        space, seed_k=3, eta=3, rung_steps=(2,), probe=fake_probe,
        save=False, log=lambda s: None,
        identity=CPU_IDENTITY, device_kind=V5E,
    )
    assert rec["tune"]["batch"] == 2


def test_successive_halving_all_dead_raises(devices, tune_dir):
    space = TuneSpace((TuneParam("p", ({"batch": 1},)),))
    with pytest.raises(RuntimeError, match="every probe"):
        successive_halving(
            space, seed_k=1, rung_steps=(2,),
            probe=lambda *a: {"value": None, "error": "x"},
            save=False, log=lambda s: None,
            identity=CPU_IDENTITY, device_kind=V5E,
        )


# -- the CPU-proxy acceptance smoke (real subprocess probes) ------------


def test_autotune_cpu_proxy_smoke(devices, tune_dir):
    """Tiny 2-point space through the REAL probe path: fresh
    subprocesses run bench.bench_gpt2 with each point, a record lands in
    the store, and a second autotune() call re-searches NOTHING."""
    space = gpt2_space(tiny=True)
    assert space.size == 2
    rec = autotune(
        model="gpt2", space=space, seed_k=2, rung_steps=(2,),
        warmup=1, probe_timeout_s=240.0, log=lambda s: None,
        device_kind=V5E,
    )
    assert rec["probes"] == 2
    assert rec["value"] and rec["value"] > 0
    assert rec["tune"]["hidden"] == 64  # the tiny proxy dims
    files = list(tune_dir.glob("*.json"))
    assert len(files) == 1
    on_disk = json.loads(files[0].read_text())
    assert on_disk["value"] == rec["value"]

    again = autotune(model="gpt2", space=space)
    assert again["probes"] == 0 and again.get("reused") is True
    assert again["tune"] == rec["tune"]
