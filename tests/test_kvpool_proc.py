"""Fleet KV page tier — cross-process proofs (spawn-heavy, heavy tail).

The unit zone (codec, pool protocol, in-process loop tier) lives in
``tests/test_kvpool.py``; this file proves the tier across REAL process
boundaries, which is the whole point of ISSUE 16:

- session migration (tier-1): kill a session's sticky worker after
  turn 1 — turn 2 lands on a replica that never saw the session and is
  served from POOL-TRANSFERRED pages, bit-equal to the cold oracle,
  with the transfer wall time visible in the worker's
  ``serve/kvstore/wire`` goodput bucket;
- disaggregated prefill (tier-1): a prefill replica pushes its
  handoff's pages to the pool and the router routes only a lightweight
  ``"pages"`` notice — the decode WORKER PROCESS imports the chain on
  admit, so prefilled KV never rides a pickled SUBMIT frame;
- fleet hit-rate parity (``slow``): an 87.5%-shared-prefix trace over
  two worker processes sharing one pool reuses exactly as many prompt
  tokens as the single-replica baseline;
- router-driven migration under heal, int8 layout (``slow``): the
  sticky replica dies mid-conversation, supervision respawns it, and
  turn 2 re-routes + serves from pooled int8 pages — exactly one typed
  result per request;
- TTFT bench guard (``slow``): on the CPU proxy, a prefix served from
  pool-transferred pages beats the cold prefill at p50 even after
  paying the wire cost.
"""

import time

import numpy as np
import pytest

from rocket_tpu.serve import (
    Completed,
    FleetRouter,
    KVPagePool,
    KVPoolClient,
    PrefillReplica,
    ProcReplica,
    Request,
    SharedPrefixIndex,
    WorkerSpec,
)
from rocket_tpu.testing import workers as tw

pytestmark = [pytest.mark.kvpool, pytest.mark.procfleet,
              pytest.mark.serving]

BUILDER = "rocket_tpu.testing.workers:build_tiny_loop"
SPAWN_S = 240.0     # worker spawn includes a jax import + model init
PAGE = 3            # pool/store page size for the tiny worker pair


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(17)
    return rng.integers(1, tw.VOCAB, size=(8, tw.P)).astype(np.int32)


def _await_corpse(rep, timeout=10.0):
    """SIGKILL delivery is asynchronous — wait for the pid to reap."""
    deadline = time.monotonic() + timeout
    while rep.proc.poll() is None and time.monotonic() < deadline:
        time.sleep(0.01)
    assert rep.proc.poll() is not None, "worker survived SIGKILL"


def _assert_exactly_once(results, rids):
    got = sorted(r.rid for r in results)
    assert got == sorted(rids), (got, sorted(rids))


def _pump_until_done(rep_or_router, want, max_rounds=400):
    out = []
    for _ in range(max_rounds):
        busy = rep_or_router.pump()
        out.extend(rep_or_router.drain_results())
        if len(out) >= want and not busy:
            return out
    raise AssertionError(f"only {len(out)}/{want} results after "
                         f"{max_rounds} rounds")


def _cold_serve(prompt_rows, int8=None):
    """rid-index -> tokens from a store-less, pool-less in-process loop
    over the SAME builder the workers run — the cold oracle (the
    local-hit oracle is bit-equal to it by the kvstore contract)."""
    loop = tw.build_tiny_loop(kv_cache_int8=int8)
    try:
        for i, p in enumerate(prompt_rows):
            assert loop.submit(Request(rid=i, prompt=p)) is None
        out = {}
        for res in loop.run_until_idle():
            assert isinstance(res, Completed), res
            out[res.rid] = np.asarray(res.tokens)
    finally:
        loop.close()
    return out


# -- session migration (tier-1 acceptance) -----------------------------------


def test_session_migration_transferred_pages_bit_equal(prompts):
    """Acceptance: the session's sticky worker is SIGKILLed after
    turn 1; turn 2 (a superset prompt) is served by a replica that never
    saw the session — its only warm path is the fleet pool — and the
    tokens are bit-equal to the cold oracle, with the transfer visible
    in both the pool counters and the worker's wire goodput bucket."""
    pool = KVPagePool(page_tokens=PAGE)
    spec = WorkerSpec(builder=BUILDER,
                      kwargs={"kvstore_page_tokens": PAGE},
                      kvpool=pool.address)
    a = ProcReplica(spec, "mig-a", spawn_timeout_s=SPAWN_S,
                    rpc_timeout_s=SPAWN_S)
    b = ProcReplica(spec, "mig-b", spawn_timeout_s=SPAWN_S,
                    rpc_timeout_s=SPAWN_S)
    try:
        # turn 1 on the session's sticky replica
        assert a.submit(Request(rid="t1", prompt=prompts[0], session="s0"))
        (r1,) = _pump_until_done(a, 1)
        assert isinstance(r1, Completed)
        full = np.asarray(r1.tokens)          # the finished 24-token row
        # the worker exported the finished row's chain pool-ward
        assert pool.snapshot()["pages_pushed"] > 0

        # mid-session host loss — nothing supervisor-side is told
        a.kill()
        _await_corpse(a)
        assert not a.probe()

        # turn 2: the conversation continues with a superset prompt on
        # the OTHER replica, whose local store has never held a page
        p2 = full[:16].astype(np.int32)
        assert b.submit(Request(rid="t2", prompt=p2, session="s0"))
        (r2,) = _pump_until_done(b, 1)
        assert isinstance(r2, Completed)
        assert np.array_equal(np.asarray(r2.tokens),
                              _cold_serve([p2])[0])

        # served FROM TRANSFERRED PAGES, not cold: 5 full pages of the
        # 16-token prompt (limit = len - 1) came through the pool
        assert b.counters["pool_hits"] == 1.0
        assert b.counters["pool_hit_tokens"] == float((16 - 1) // PAGE
                                                      * PAGE)
        snap = pool.snapshot()
        assert snap["fetch_hits"] >= 1 and snap["bytes_out"] > 0
        # transfer wall time landed in the worker's wire goodput bucket
        stats = b.collect()
        assert stats is not None
        assert stats["goodput"].get("serve/kvstore/wire_s", 0.0) > 0.0
    finally:
        a.close()
        b.close()
        pool.close()


# -- disaggregated prefill (tier-1 acceptance) --------------------------------


def test_prefill_disaggregation_via_pool(prompts):
    """Acceptance: with a pool-armed prefill lane, the router never
    moves a pickled KVHandoff — each prefill pushes its pages to the
    pool and only a ``"pages"`` notice crosses; the decode WORKER
    PROCESS imports the chain on admit and serves bit-equal."""
    from rocket_tpu.models.generate import ContinuousBatcher

    pool = KVPagePool(page_tokens=PAGE)
    spec = WorkerSpec(builder=BUILDER,
                      kwargs={"kvstore_page_tokens": PAGE},
                      kvpool=pool.address)
    decode = ProcReplica(spec, "dis-d0", spawn_timeout_s=SPAWN_S,
                         rpc_timeout_s=SPAWN_S)
    model, draft, params, dparams = tw.tiny_models()

    def bat_factory():
        return ContinuousBatcher(model, draft, params, dparams,
                                 total_len=tw.TOTAL, n_draft=tw.NDRAFT,
                                 eos_token=None)

    prefill = PrefillReplica(bat_factory, "dis-p0",
                             kvpool=KVPoolClient.connect(pool.address),
                             page_tokens=PAGE)
    router = FleetRouter([decode], prefill_replicas=[prefill],
                         prefill_threshold=None)
    rids = [f"d{i}" for i in range(3)]
    oracle = _cold_serve([prompts[i] for i in range(3)])
    try:
        for i, rid in enumerate(rids):
            assert router.submit(Request(rid=rid, prompt=prompts[i])) \
                is None
        results = router.run_until_idle()
        _assert_exactly_once(results, rids)
        assert router.counters.pool_handoffs == 3
        assert router.counters.handoffs == 0    # no pickled handoff moved
        for res in results:
            assert isinstance(res, Completed), res
            i = int(res.rid[1:])
            assert np.array_equal(np.asarray(res.tokens), oracle[i]), \
                res.rid
        # the decode worker imported every chain from the pool: 2 full
        # pages per 8-token prompt (the handoff covers prompt + 1 token)
        assert decode.counters["pool_hits"] == 3.0
        assert decode.counters["pool_hit_tokens"] == 3.0 * (tw.P // PAGE
                                                            * PAGE)
        snap = pool.snapshot()
        assert snap["pushes"] >= 3 and snap["fetch_hits"] >= 3
    finally:
        router.close()
        pool.close()


# -- fleet-wide hit-rate parity (slow acceptance) -----------------------------


@pytest.mark.slow
def test_fleet_hit_rate_matches_single_replica():
    """Acceptance: an 87.5%-shared-prefix trace (14 of 16 prompt tokens
    shared) across TWO worker processes sharing one pool reuses exactly
    as many prompt tokens as the single-replica baseline — local hits
    plus pool hits together close the cross-process gap — and the
    transfer cost shows up in the workers' wire goodput bucket."""
    PAGE2, PROMPT, SHARED, N = 2, 16, 14, 8
    rng = np.random.default_rng(23)
    header = rng.integers(1, tw.VOCAB, size=SHARED)

    def turn(i):
        tail = np.random.default_rng(100 + i).integers(
            1, tw.VOCAB, size=PROMPT - SHARED)
        return np.concatenate([header, tail]).astype(np.int32)

    trace = [turn(i) for i in range(N)]

    # single-replica baseline: one in-process loop, same builder
    base_loop = tw.build_tiny_loop(kvstore_page_tokens=PAGE2)
    base_tokens = {}
    try:
        assert base_loop.submit(Request(rid=0, prompt=trace[0])) is None
        for res in base_loop.run_until_idle():
            base_tokens[res.rid] = np.asarray(res.tokens)
        for i in range(1, N):
            assert base_loop.submit(Request(rid=i, prompt=trace[i])) \
                is None
        for res in base_loop.run_until_idle():
            base_tokens[res.rid] = np.asarray(res.tokens)
        base = base_loop.counters.snapshot()
    finally:
        base_loop.close()
    base_warm = base["kv_hit_tokens"]
    assert base_warm == (N - 1) * SHARED    # every follow-up fully warm

    pool = KVPagePool(page_tokens=PAGE2)
    spec = WorkerSpec(builder=BUILDER,
                      kwargs={"kvstore_page_tokens": PAGE2},
                      kvpool=pool.address)
    # NO prefix index here, deliberately: the route-by-pages hint would
    # sticky every shared-prefix turn onto the one page-holder replica.
    # Pure least-loaded routing spreads the trace, so parity can only
    # hold if the pool closes the cross-process gap.
    reps = [ProcReplica(spec, f"hr-{i}", spawn_timeout_s=SPAWN_S,
                        rpc_timeout_s=SPAWN_S)
            for i in range(2)]
    router = FleetRouter(reps)
    try:
        assert router.submit(Request(rid=0, prompt=trace[0])) is None
        results = router.run_until_idle()
        for i in range(1, N):
            assert router.submit(Request(rid=i, prompt=trace[i])) is None
        results += router.run_until_idle()
        _assert_exactly_once(results, list(range(N)))
        for res in results:
            assert isinstance(res, Completed), res
            assert np.array_equal(np.asarray(res.tokens),
                                  base_tokens[res.rid]), res.rid
        # both processes served part of the trace
        assert all(rep.counters["completed"] >= 1 for rep in reps)
        # pool-fetched pages land in the local store and serve through
        # the normal kv-hit path, so pool_hit_tokens is an ATTRIBUTION
        # subset of kv_hit_tokens (how many warm tokens crossed the
        # wire), never an addition to it
        fleet_warm = sum(rep.counters["kv_hit_tokens"] for rep in reps)
        # parity: the pool closes the cross-process gap exactly — the
        # fleet reuses the same warm tokens the single replica did
        assert fleet_warm == base_warm, (fleet_warm, base_warm)
        # ...and at least one full shared header came cross-process
        assert sum(rep.counters["pool_hit_tokens"]
                   for rep in reps) >= SHARED
        # the transfer cost is visible, not hidden: some worker charged
        # wall time to the serve/kvstore/wire goodput bucket
        wire_s = []
        for rep in reps:
            stats = rep.collect()
            assert stats is not None
            wire_s.append(stats["goodput"].get("serve/kvstore/wire_s",
                                               0.0))
        assert max(wire_s) > 0.0, wire_s
        assert pool.snapshot()["bytes_moved"] > 0
    finally:
        router.close()
        pool.close()


# -- router-driven migration under heal, int8 (slow acceptance) ---------------


@pytest.mark.slow
@pytest.mark.resilience
def test_session_migration_router_heal_int8(prompts):
    """Acceptance: full fleet machinery, int8 KV layout.  The session's
    sticky replica is SIGKILLed mid-conversation; supervision heals it
    while turn 2 re-routes to the survivor, which imports the pooled
    int8 pages (payload + rank-4 f32 scales crossed the wire) and
    serves bit-equal to the int8 cold oracle — exactly one typed result
    per request."""
    pool = KVPagePool(page_tokens=PAGE)
    index = SharedPrefixIndex(page_tokens=PAGE)
    spec = WorkerSpec(builder=BUILDER,
                      kwargs={"kvstore_page_tokens": PAGE,
                              "kv_cache_int8": True},
                      kvpool=pool.address)
    reps = [ProcReplica(spec, f"m8-{i}", spawn_timeout_s=SPAWN_S,
                        rpc_timeout_s=SPAWN_S, prefix_index=index)
            for i in range(2)]
    router = FleetRouter(reps, prefix_index=index)
    try:
        assert router.submit(Request(rid="i1", prompt=prompts[0],
                                     session="s8")) is None
        results = router.run_until_idle()
        (r1,) = results
        assert isinstance(r1, Completed)
        full = np.asarray(r1.tokens)
        sticky_id = router._affinity["s8"]
        (sticky,) = [r for r in reps if r.replica_id == sticky_id]

        sticky.kill()
        _await_corpse(sticky)

        p2 = full[:16].astype(np.int32)
        assert router.submit(Request(rid="i2", prompt=p2,
                                     session="s8")) is None
        results += router.run_until_idle()
        _assert_exactly_once(results, ["i1", "i2"])
        (r2,) = [r for r in results if r.rid == "i2"]
        assert isinstance(r2, Completed)
        assert np.array_equal(np.asarray(r2.tokens),
                              _cold_serve([p2], int8=True)[0])
        # supervision healed the killed sticky; the survivor served the
        # migrated turn from pooled int8 pages
        assert router.counters.heals == 1
        assert sticky.spawns == 2
        assert sum(rep.counters.get("pool_hits", 0.0)
                   for rep in reps) >= 1
        assert pool.snapshot()["fetch_hits"] >= 1
    finally:
        router.close()
        pool.close()


# -- TTFT bench guard (slow) --------------------------------------------------


def _proxy_models(hidden=128, max_seq=272, prompt=256):
    import jax

    from rocket_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    cfg = dict(vocab_size=64, hidden=hidden, n_layers=2, n_heads=4,
               max_seq=max_seq)
    out = []
    for seed in (1, 7):
        m = TransformerLM(TransformerConfig(**cfg))
        p = m.init(
            jax.random.PRNGKey(seed),
            {"tokens": np.zeros((1, prompt), np.int32),
             "positions": np.zeros((1, prompt), np.int32)},
        )["params"]
        out.append((m, p))
    (model, params), (_, dparams) = out
    return model, model, params, dparams


@pytest.mark.slow
def test_pool_transferred_prefix_is_not_prefilled():
    """A prefix imported from POOL-TRANSFERRED pages is not prefilled
    again: every turn imports the whole shared header and only the tail
    goes through the model.  (Was "pool TTFT p50 beats cold by 0.25 x the
    shared fraction" on a CPU's clock; the tokens spared are what that
    time stood for, and whether the wire costs less than the prefill it
    avoids is the chip's to say: not measured.)  Every turn runs on a
    FRESH loop with an empty local store, so the only warm path is the
    pool socket."""
    from rocket_tpu.models.generate import ContinuousBatcher
    from rocket_tpu.serve import ServingLoop
    from rocket_tpu.serve.kvstore import PrefixKVStore

    PROMPT, PAGE_B, SHARED, NEW, TURNS = 256, 32, 224, 8, 7
    models = _proxy_models(prompt=PROMPT, max_seq=PROMPT + 16)
    model, draft, params, dparams = models
    rng = np.random.default_rng(5)
    header = rng.integers(1, 64, size=SHARED)

    def turn(t):
        tail = np.random.default_rng(100 + t).integers(
            1, 64, size=PROMPT - SHARED)
        return np.concatenate([header, tail]).astype(np.int32)

    def factory():
        return ContinuousBatcher(model, draft, params, dparams,
                                 total_len=PROMPT + NEW,
                                 n_draft=tw.NDRAFT, eos_token=None)

    def run(pool):
        """One pass over the trace; each turn gets a FRESH loop (empty
        local store) so warm pages can only arrive through the pool."""
        hits = hit_tokens = 0
        for t in range(TURNS):
            kv = PrefixKVStore(page_tokens=PAGE_B,
                               capacity_bytes=1 << 30) \
                if pool is not None else None
            client = KVPoolClient.connect(pool.address) \
                if pool is not None else None
            loop = ServingLoop(
                factory, max_batch=1, queue_capacity=4,
                kvstore=kv, kvpool=client)
            try:
                assert loop.submit(Request(rid=t, prompt=turn(t))) is None
                loop.run_until_idle(max_rounds=1_000_000)
                hits += int(loop.counters.pool_hits)
                hit_tokens += int(loop.counters.pool_hit_tokens)
            finally:
                loop.close()
        return hits, hit_tokens

    pool = KVPagePool(page_tokens=PAGE_B)
    try:
        run(pool)                       # compile both paths + seed pool
        assert run(None) == (0, 0)
        # the pool already holds the header after the seeding pass, so
        # every warm turn imports it whole and prefills the tail alone
        assert run(pool) == (TURNS, TURNS * SHARED)
    finally:
        pool.close()
