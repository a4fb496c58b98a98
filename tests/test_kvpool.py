"""Fleet KV page tier tests — the fast in-process zone.

Layers (spawn-heavy cross-process proofs live in test_kvpool_proc.py):

- units: the pack_arrays/unpack_arrays binary ndarray codec (bit-exact
  across dtypes, 0-d scalars, non-contiguous input, empty arrays) and
  the page-chain codec over real prefill pages, f32 AND int8+rank-4-
  scale layouts;
- the pool service: push/fetch/NACK/partial-chain over a real socket,
  counters, client-side push dedup, dead-pool degradation;
- staleness hardening (ISSUE 16 satellite): a store eviction surfaces
  through drain_evicted_hashes and SharedPrefixIndex.forget drops the
  stranded claim, counting pages_stale — the regression for hints
  silently outliving worker-side eviction;
- the loop tier: two in-process ServingLoops sharing one pool — cold
  serve on A, pool-transferred serve on B bit-equal to the oracle; and
  the armed-but-idle guard (zero new jit traces, <5% host overhead per
  decode round);
- export: kvpool occupancy/capacity gauges merge by MAX while counters
  SUM, and per-replica kvstore occupancies still SUM.
"""

import numpy as np
import pytest

import jax

from rocket_tpu.models.generate import ContinuousBatcher, _spec_round
from rocket_tpu.serve import Completed, Request, ServingLoop
from rocket_tpu.serve.kvpool import (
    KVPagePool,
    KVPoolClient,
    decode_page_chain,
    encode_page_chain,
    register_kvpool_source,
)
from rocket_tpu.serve.kvstore import (
    PrefixKVStore,
    SharedPrefixIndex,
    page_hashes,
)
from rocket_tpu.utils.framing import pack_arrays, unpack_arrays

pytestmark = [pytest.mark.kvpool, pytest.mark.serving]

B, P, TOTAL, NDRAFT, PAGE = 3, 12, 24, 4, 4


def _lm(seed=1, **kw):
    from rocket_tpu.models.transformer import (TransformerConfig,
                                               TransformerLM)
    cfg = TransformerConfig(
        vocab_size=64, hidden=32, n_layers=2, n_heads=4, max_seq=64, **kw
    )
    m = TransformerLM(cfg)
    p = m.init(
        jax.random.PRNGKey(seed),
        {"tokens": np.zeros((1, P), np.int32),
         "positions": np.zeros((1, P), np.int32)},
    )["params"]
    return m, p


def _models(int8=False):
    kw = {"kv_cache_int8": True} if int8 else {}
    model, params = _lm(seed=1, **kw)
    draft, _ = _lm(seed=1, **kw)
    _, dparams = _lm(seed=7, **kw)
    return model, draft, params, dparams


def _bat(models, **kw):
    model, draft, params, dparams = models
    return ContinuousBatcher(model, draft, params, dparams,
                             total_len=TOTAL, n_draft=NDRAFT,
                             eos_token=None, **kw)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(13)
    return rng.integers(1, 64, size=(8, P)).astype(np.int32)


def _chain(models, prompt):
    """(hashes, pages) for one prompt's prefilled full pages — hashed
    over the handoff buffer (prompt + first emitted token), the same
    rule as PrefixKVStore.insert."""
    host = _bat(models).prefill_handoff(prompt[None, :]).to_host()
    pages = host.split_pages(PAGE)
    hashes = page_hashes(
        np.asarray(host.buf)[0], PAGE,
        limit=int(np.asarray(host.n_tok)[0]) - 1,
    )[:len(pages)]
    return hashes, pages


# -- units: the binary ndarray codec -------------------------------------


class TestPackArrays:
    def test_round_trip_bit_exact_across_dtypes(self):
        rng = np.random.default_rng(0)
        arrays = [
            rng.standard_normal((2, 3, 4, 5)).astype(np.float32),
            (rng.standard_normal((1, 8, 4, 1)) * 10).astype(np.int8),
            rng.standard_normal((1, 8, 4, 1)).astype(np.float32),  # scales
            np.asarray(17, np.int32),                 # 0-d cache_index
            np.arange(6, dtype=np.int64),
            np.array([], dtype=np.float16),
            np.array([[True, False], [False, True]]),
        ]
        out = unpack_arrays(pack_arrays(arrays))
        assert len(out) == len(arrays)
        for a, b in zip(arrays, out):
            assert a.shape == b.shape and a.dtype == b.dtype
            assert np.array_equal(a, b)
            assert b.tobytes() == a.tobytes()  # bit-exact, NaN-safe

    def test_non_contiguous_input_and_owned_output(self):
        base = np.arange(24, dtype=np.float32).reshape(4, 6)
        view = base[:, ::2]                  # non-contiguous
        (out,) = unpack_arrays(pack_arrays([view]))
        assert np.array_equal(out, view)
        # default decode COPIES: the page must not pin the frame alive,
        # and consumers may mutate it
        out[0, 0] = -1.0                     # writable => owned

    def test_no_per_array_pickle_overhead(self):
        # the whole point: payload section is the raw buffer bytes, so
        # blob size is header + exactly sum(nbytes)
        arrays = [np.zeros((64, 64), np.float32), np.zeros(7, np.int8)]
        blob = pack_arrays(arrays)
        payload = sum(a.nbytes for a in arrays)
        assert payload <= len(blob) <= payload + 128


# -- units: the page-chain codec -----------------------------------------


class TestPageChainCodec:
    @pytest.mark.parametrize("int8", [False, True])
    def test_round_trip_bit_exact(self, prompts, int8):
        hashes, pages = _chain(_models(int8), prompts[0])
        assert len(pages) >= 2
        blob = encode_page_chain(hashes, pages)
        h2, p2 = decode_page_chain(blob)
        assert h2 == hashes and len(p2) == len(pages)
        for a, b in zip(pages, p2):
            la = jax.tree_util.tree_leaves((a.tokens, a.cache_t, a.cache_d))
            lb = jax.tree_util.tree_leaves((b.tokens, b.cache_t, b.cache_d))
            for x, y in zip(la, lb):
                x, y = np.asarray(x), np.asarray(y)
                assert x.shape == y.shape and x.dtype == y.dtype
                assert np.array_equal(x, y)
        if int8:
            leaves = [np.asarray(leaf) for p in p2 for leaf in
                      jax.tree_util.tree_leaves((p.cache_t, p.cache_d))]
            assert any(a.ndim == 4 and a.dtype == np.int8 for a in leaves)
            # int8 payload travels with its rank-4 f32 scale leaves
            assert any(a.ndim == 4 and a.dtype == np.float32
                       for a in leaves)

    def test_int8_wire_is_smaller(self, prompts):
        _, pages_f32 = _chain(_models(False), prompts[0])
        h8, pages_i8 = _chain(_models(True), prompts[0])
        f32 = len(encode_page_chain([b"x"] * len(pages_f32), pages_f32))
        i8 = len(encode_page_chain(h8, pages_i8))
        assert i8 < f32 * 0.6  # ~2.7x smaller at real layer shapes

    def test_length_mismatch_raises(self, prompts):
        hashes, pages = _chain(_models(), prompts[0])
        with pytest.raises(ValueError):
            encode_page_chain(hashes[:-1], pages)


# -- the pool service ----------------------------------------------------


class TestKVPagePool:
    def test_push_fetch_partial_nack_and_counters(self, prompts):
        models = _models()
        hashes, pages = _chain(models, prompts[0])
        pool = KVPagePool(page_tokens=PAGE, capacity_bytes=1 << 22)
        try:
            cli = KVPoolClient.connect(pool.address)
            assert cli.push(hashes, pages) == len(pages)
            # client-side dedup: an identical chain never re-crosses
            assert cli.push(hashes, pages) == 0
            assert pool.snapshot()["pushes"] == 1.0

            got = cli.fetch(hashes)
            assert got is not None and len(got) == len(pages)
            assert np.array_equal(
                np.asarray(got[0].tokens), np.asarray(pages[0].tokens))
            # a longer chain fetches its stored prefix (partial hit)
            part = cli.fetch(list(hashes) + [b"\x00" * 16])
            assert part is not None and len(part) == len(pages)
            # total miss => NACK => None, and the pool counts it
            assert cli.fetch([b"\x01" * 16]) is None
            snap = pool.snapshot()
            assert snap["fetch_hits"] == 2.0 and snap["nacks"] == 1.0
            assert snap["bytes_in"] > 0 and snap["bytes_out"] > 0
            assert snap["bytes_moved"] == snap["bytes_in"] \
                + snap["bytes_out"]
            assert snap["pages"] == float(len(pages))
            csnap = cli.snapshot()
            assert csnap["hits"] == 2.0 and csnap["nacks"] == 1.0
            assert csnap["bytes_moved"] > 0
            cli.close()
        finally:
            pool.close()

    def test_nack_clears_push_dedup(self, prompts):
        # pool-side eviction means "pushed before" no longer implies
        # "present": after any NACK the client must re-push on request
        hashes, pages = _chain(_models(), prompts[0])
        pool = KVPagePool(page_tokens=PAGE, capacity_bytes=1 << 22)
        try:
            cli = KVPoolClient.connect(pool.address)
            assert cli.push(hashes, pages) == len(pages)
            assert cli.fetch([b"\x02" * 16]) is None  # NACK
            pool._store._table.clear()                # simulate eviction
            pool._store.occupancy_bytes = 0
            assert cli.push(hashes, pages) == len(pages)  # re-pushed
            cli.close()
        finally:
            pool.close()

    def test_dead_pool_degrades_not_raises(self, prompts):
        hashes, pages = _chain(_models(), prompts[0])
        pool = KVPagePool(page_tokens=PAGE)
        cli = KVPoolClient.connect(pool.address, timeout=2.0)
        pool.close()
        # first call eats the socket error, marks dead; later calls
        # short-circuit — never an exception on the serving path
        assert cli.fetch(hashes) is None
        assert cli.push(hashes, pages) == 0
        assert cli.fetch(hashes) is None
        cli.close()

    def test_match_hashes_same_discipline_as_lookup(self, prompts):
        models = _models()
        hashes, pages = _chain(models, prompts[0])
        store = PrefixKVStore(page_tokens=PAGE)
        store.put_pages(hashes, pages)
        m = store.match_hashes(list(hashes))
        assert m is not None and m.hashes == list(hashes)
        # matched entries are pinned until release — same as lookup
        assert all(store._table[h].pins == 1 for h in hashes)
        store.release(m)
        assert all(store._table[h].pins == 0 for h in hashes)
        m2 = store.match_hashes([b"\x03" * 16])
        assert m2 is None and store.misses == 1


# -- staleness hardening (satellite) -------------------------------------


class TestStalenessFeedback:
    def test_eviction_surfaces_through_drain(self, prompts):
        models = _models()
        ha, pa = _chain(models, prompts[0])
        hb, pb = _chain(models, prompts[1])
        # capacity for one chain only: storing B must evict A's pages
        # (same-chain puts cannot self-evict — own-chain pinning)
        cap = int(sum(p.nbytes for p in pa))
        store = PrefixKVStore(page_tokens=PAGE, capacity_bytes=cap)
        store.put_pages(ha, pa)
        assert store.drain_evicted_hashes() == []
        store.put_pages(hb, pb)
        assert store.evictions > 0
        evicted = store.drain_evicted_hashes()
        assert evicted and set(evicted) <= set(ha)
        assert store.drain_evicted_hashes() == []  # return-and-clear

    def test_forget_degrades_hint_and_counts_stale(self, prompts):
        """Regression: a worker-side eviction must NOT strand the
        supervisor-side hint — forget() drops the claim so best_replica
        degrades to None (=> cold prefill), counting pages_stale."""
        idx = SharedPrefixIndex(page_tokens=PAGE)
        toks = prompts[0]
        hashes = page_hashes(toks, PAGE, limit=toks.shape[0] - 1)
        idx.note("r0", hashes)
        assert idx.best_replica(toks) == "r0"
        # the replica evicts the chain root; its STEP ships the delta
        dropped = idx.forget("r0", [hashes[0]])
        assert dropped == 1 and idx.pages_stale == 1
        assert idx.best_replica(toks) is None  # hint gone, not an error
        assert idx.snapshot()["pages_stale"] == 1.0

    def test_forget_is_per_replica(self, prompts):
        idx = SharedPrefixIndex(page_tokens=PAGE)
        toks = prompts[0]
        hashes = page_hashes(toks, PAGE, limit=toks.shape[0] - 1)
        idx.note("r0", hashes)
        idx.note("r1", hashes)
        idx.forget("r0", hashes)
        assert idx.best_replica(toks) == "r1"  # other replica unaffected
        # forgetting unknown claims is a no-op, not an error
        assert idx.forget("r0", hashes) == 0


# -- the loop tier: cross-loop transfer + armed-but-idle guard -----------


def _tiny_loop(**kw):
    from rocket_tpu.testing.workers import build_tiny_loop
    return build_tiny_loop(**kw)


class TestLoopPoolTier:
    def test_two_loops_share_pages_bit_equal(self):
        from rocket_tpu.testing.workers import P as WP
        rng = np.random.default_rng(42)
        prompt = rng.integers(1, 60, size=WP).astype(np.int32)

        oracle = _tiny_loop()
        oracle.submit(Request(rid="o", prompt=prompt))
        ref = {r.rid: r for r in oracle.run_until_idle()}["o"]
        oracle.close()
        assert isinstance(ref, Completed)

        pool = KVPagePool(page_tokens=3, capacity_bytes=1 << 22)
        try:
            a = _tiny_loop(kvstore_page_tokens=3, kvpool_addr=pool.address)
            b = _tiny_loop(kvstore_page_tokens=3, kvpool_addr=pool.address)
            a.submit(Request(rid="a", prompt=prompt))
            ra = {r.rid: r for r in a.run_until_idle()}["a"]
            assert np.array_equal(ra.tokens, ref.tokens)   # cold == oracle
            assert pool.snapshot()["pages_pushed"] > 0     # retire pushed

            b.submit(Request(rid="b", prompt=prompt))
            rb = {r.rid: r for r in b.run_until_idle()}["b"]
            # B never prefilled this prompt: pages came through the pool
            assert np.array_equal(rb.tokens, ref.tokens)
            assert b.counters.pool_hits >= 1
            assert b.counters.pool_hit_tokens > 0
            assert pool.snapshot()["bytes_out"] > 0
            a.close()
            b.close()
        finally:
            pool.close()

    def test_pool_miss_degrades_to_cold_prefill(self):
        from rocket_tpu.testing.workers import P as WP
        rng = np.random.default_rng(7)
        prompt = rng.integers(1, 60, size=WP).astype(np.int32)
        pool = KVPagePool(page_tokens=3)
        try:
            loop = _tiny_loop(kvstore_page_tokens=3,
                              kvpool_addr=pool.address)
            loop.submit(Request(rid="x", prompt=prompt))
            res = {r.rid: r for r in loop.run_until_idle()}["x"]
            assert isinstance(res, Completed)     # NACK => cold, no error
            assert loop.counters.pool_nacks >= 1
            assert loop.counters.pool_hits == 0
            loop.close()
        finally:
            pool.close()

    def test_kvpool_requires_kvstore(self):
        with pytest.raises(ValueError):
            ServingLoop(lambda: None, max_batch=1, kvpool=object())

    def test_armed_but_idle_zero_traces_reads_and_dispatches(self):
        from rocket_tpu.testing.workers import B as WB, P as WP
        rng = np.random.default_rng(3)
        prompts8 = rng.integers(1, 60, size=(WB, WP)).astype(np.int32)
        rounds = 8

        def round_counts(loop):
            """Blocking host reads and rounds dispatched over `rounds`
            decode rounds, as `ServeCounters` has them."""
            for i in range(WB):
                loop.submit(Request(rid=i, prompt=prompts8[i]))
            loop.run_round()  # admits + settles
            c = loop.counters
            before = c.host_fetches, c.rounds
            for _ in range(rounds):
                loop.run_round()
            out = c.host_fetches - before[0], c.rounds - before[1]
            loop.run_until_idle()
            return out

        bare_loop = _tiny_loop(kvstore_page_tokens=3)
        bare = round_counts(bare_loop)
        bare_loop.close()

        pool = KVPagePool(page_tokens=3)
        try:
            traces_before = _spec_round._cache_size()
            armed_loop = _tiny_loop(kvstore_page_tokens=3,
                                    kvpool_addr=pool.address)
            armed = round_counts(armed_loop)
            # the pool added ZERO traced step bodies
            assert _spec_round._cache_size() == traces_before
            armed_loop.close()
        finally:
            pool.close()
        # Was "pool-armed round <= 1.05 x bare" on the clock.  The pool
        # client is untouched mid-decode: a round that it slowed would
        # read the device or dispatch once more, and it does neither.
        assert armed == bare
        assert armed[1] == rounds


# -- export / merge semantics --------------------------------------------


class TestKVPoolExport:
    def test_register_source_and_prometheus_names(self, prompts):
        from rocket_tpu.observe.export import collect, unregister_source
        from rocket_tpu.observe.export import prometheus_text
        hashes, pages = _chain(_models(), prompts[0])
        pool = KVPagePool(page_tokens=PAGE)
        try:
            name = register_kvpool_source(pool)
            cli = KVPoolClient.connect(pool.address)
            cli.push(hashes, pages)
            snap = collect()
            assert snap["serve_kvpool/pushes"] == 1.0
            assert snap["serve_kvpool/occupancy_bytes"] > 0
            text = prometheus_text({k: v for k, v in snap.items()
                                    if k.startswith("serve_kvpool/")})
            assert "rocket_tpu_serve_kvpool_bytes_moved" in text
            cli.close()
        finally:
            unregister_source("serve_kvpool")
            pool.close()

    def test_merge_pool_gauges_max_counters_sum(self):
        from rocket_tpu.observe.export import merge_counters
        a = {"serve_kvpool/fetches": 3.0,
             "serve_kvpool/occupancy_bytes": 100.0,
             "serve_kvpool/capacity_bytes": 1000.0,
             "serve_kvstore/occupancy_bytes": 40.0}
        b = {"serve_kvpool/fetches": 2.0,
             "serve_kvpool/occupancy_bytes": 70.0,
             "serve_kvpool/capacity_bytes": 1000.0,
             "serve_kvstore/occupancy_bytes": 60.0}
        m = merge_counters([a, b])
        assert m["serve_kvpool/fetches"] == 5.0            # counter: SUM
        assert m["serve_kvpool/occupancy_bytes"] == 100.0  # gauge: MAX
        assert m["serve_kvpool/capacity_bytes"] == 1000.0  # one pool
        # per-replica kvstore occupancies are DISTINCT stores: still SUM
        assert m["serve_kvstore/occupancy_bytes"] == 100.0
