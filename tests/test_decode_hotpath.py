"""Decode hot path: KV-cached beam search + round-granular continuous
batching (PR 6).

The serving-side contracts under test:

- :func:`beam_search_cached` is bit-equal (tokens) to the O(T)
  re-decode oracle :func:`beam_search`, from ONE prompt prefill plus
  O(T) single-token cached forwards — proven by an instrumented proxy
  model that records every forward's token shape;
- :class:`ContinuousBatcher` (one speculative round per dispatch,
  state on device) reproduces the one-dispatch
  :func:`speculative_generate_batched` bit for bit, and a request
  admitted into a half-finished batch decodes exactly as a solo run
  without disturbing the live rows;
- the host speculative loops prefill through ``_chunked_prefill``
  (rolling-cache prompts longer than the slack no longer die) and
  their ``accepted`` stat never counts drafts an eos truncated away.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rocket_tpu.models.generate import (
    ContinuousBatcher,
    beam_search,
    beam_search_cached,
    generate,
    speculative_generate,
    speculative_generate_batched,
)
from rocket_tpu.models.transformer import TransformerConfig, TransformerLM


def _lm(seed=1, **kw):
    cfg = TransformerConfig(
        vocab_size=64, hidden=32, n_layers=2, n_heads=4, max_seq=64,
        norm="layernorm", mlp="gelu", positions="learned",
        tie_embeddings=True, use_bias=True, attention="dot", **kw,
    )
    model = TransformerLM(cfg)
    init = jnp.zeros((1, 8), jnp.int32)
    params = nn.meta.unbox(
        model.init(jax.random.PRNGKey(seed), {"tokens": init})["params"]
    )
    return model, params


def _prompt(B=3, P=8, seed=13, vocab=64):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, vocab, size=(B, P)), jnp.int32)


class TestBeamSearchCached:
    def test_matches_redecode_oracle(self, devices):
        """Tokens bit-equal to :func:`beam_search` on the same inputs;
        scores agree to float tolerance (the cached path's softmax
        reduces over the cache allocation, a different — equally
        correct — reduction shape than the full forward)."""
        model, params = _lm()
        prompt = _prompt()
        oracle_t, oracle_s = beam_search(
            model, params, prompt, 12, eos_id=63, beam_size=4)
        cached_t, cached_s = beam_search_cached(
            model, params, prompt, 12, eos_id=63, beam_size=4)
        np.testing.assert_array_equal(
            np.asarray(oracle_t), np.asarray(cached_t))
        np.testing.assert_allclose(
            np.asarray(oracle_s), np.asarray(cached_s), atol=2e-5)

    def test_matches_oracle_with_live_eos(self, devices):
        """Same equality when eos actually fires: freeze + pad behavior
        must agree, because frozen beams keep writing pad continuations
        into the cache exactly as the oracle's buffer holds them."""
        model, params = _lm()
        prompt = _prompt()
        probe, _ = beam_search(model, params, prompt, 12, eos_id=63,
                               beam_size=2)
        eos = int(np.asarray(probe)[0, 8 + 2])  # fires mid-stream
        for K in (1, 2):
            ot, os_ = beam_search(model, params, prompt, 12, eos_id=eos,
                                  beam_size=K)
            ct, cs = beam_search_cached(model, params, prompt, 12,
                                        eos_id=eos, beam_size=K)
            np.testing.assert_array_equal(np.asarray(ot), np.asarray(ct))
            np.testing.assert_allclose(np.asarray(os_), np.asarray(cs),
                                       atol=2e-5)

    def test_single_new_token_edge(self, devices):
        model, params = _lm()
        prompt = _prompt()
        ot, _ = beam_search(model, params, prompt, 1, eos_id=63, beam_size=4)
        ct, _ = beam_search_cached(model, params, prompt, 1, eos_id=63,
                                   beam_size=4)
        np.testing.assert_array_equal(np.asarray(ot), np.asarray(ct))

    def test_cached_forwards_are_single_token(self, devices):
        """Instrumented O(T) proof: a recording proxy sees NO
        full-buffer-length forward from the cached path — only the
        prompt prefill plus a single-token decode trace whose count
        does not grow with T — while the oracle's step body runs the
        full ``[B*K, P+T]`` forward."""
        model, params = _lm()
        prompt = _prompt(B=2)
        P, K = 8, 4

        class Recorder:
            # identity hash/eq: each instance is a fresh static-arg
            # cache key, so every jitted caller re-traces and the
            # trace-time apply shapes land in `calls`
            def __init__(self, inner):
                self._inner = inner
                self.calls = []

            @property
            def config(self):
                return self._inner.config

            def apply(self, variables, batch, *args, **kw):
                self.calls.append(tuple(batch["tokens"].shape))
                return self._inner.apply(variables, batch, *args, **kw)

        def decode_widths(rec):
            return [s for s in rec.calls if s[0] == 2 * K]  # B*K rows

        counts = {}
        for T in (6, 12):
            rec = Recorder(model)
            beam_search_cached(rec, params, prompt, T, eos_id=63,
                               beam_size=K)
            widths = decode_widths(rec)
            assert widths, rec.calls
            # every beam-frontier forward feeds exactly ONE token; the
            # prompt is never replayed per beam or per step
            assert all(s[1] == 1 for s in widths), rec.calls
            assert all(s[1] <= P for s in rec.calls), rec.calls
            counts[T] = len(widths)
        # the decode step is traced a constant number of times (a
        # lax.scan body), independent of T: O(T) comes from scan
        # iterations of that single-token executable
        assert counts[6] == counts[12], counts

        rec = Recorder(model)
        beam_search(rec, params, prompt, 12, eos_id=63, beam_size=K)
        assert any(s == (2 * K, P + 12) for s in rec.calls), rec.calls

    def test_requires_causal_model(self, devices):
        cfg = TransformerConfig(
            vocab_size=64, hidden=32, n_layers=2, n_heads=4, max_seq=64,
            norm="layernorm", mlp="gelu", positions="learned",
            tie_embeddings=True, use_bias=True, attention="dot",
            causal=False,
        )
        model = TransformerLM(cfg)
        prompt = jnp.zeros((1, 8), jnp.int32)
        with pytest.raises(ValueError, match="causal"):
            beam_search(model, {}, prompt, 4, eos_id=1)
        with pytest.raises(ValueError, match="causal"):
            beam_search_cached(model, {}, prompt, 4, eos_id=1)


class TestContinuousBatcher:
    def _models(self):
        model, params = _lm(seed=1)
        draft, _ = _lm(seed=1)  # same structure...
        _, draft_params = _lm(seed=7)  # ...different weights
        return model, params, draft, draft_params

    def test_step_loop_matches_one_dispatch(self, devices):
        """Driving the round-granular step API to completion reproduces
        the one-dispatch while_loop bit for bit — same prefill, same
        round body, same key threading."""
        model, params, draft, draft_params = self._models()
        prompt = _prompt(B=3)
        NEW = 16
        toks, stats = speculative_generate_batched(
            model, params, draft, draft_params, prompt, NEW,
            n_draft=4, return_stats=True,
        )
        bat = ContinuousBatcher(model, draft, params, draft_params,
                                total_len=8 + NEW, n_draft=4)
        bat.start(prompt)
        steps = 0
        while not bat.all_done:
            bat.step()
            steps += 1
            assert steps < 100
        for r in range(3):
            row, n = bat.row_tokens(r)
            np.testing.assert_array_equal(row, np.asarray(toks)[r])
            assert n == 8 + NEW  # no eos: every row fills its buffer
        st = bat.stats()
        assert st["rounds"] == int(stats["rounds"]) == steps
        np.testing.assert_array_equal(st["drafted"],
                                      np.asarray(stats["drafted"]))
        np.testing.assert_array_equal(st["accepted"],
                                      np.asarray(stats["accepted"]))

    def test_admit_mid_batch_matches_solo_run(self, devices):
        """A request admitted into a half-finished batch decodes to
        completion exactly as a solo one-dispatch run — and the rows it
        joined are not disturbed."""
        model, params, draft, draft_params = self._models()
        prompt = _prompt(B=2)
        NEW = 16
        newcomer = _prompt(B=1, seed=99)[0]

        baseline = np.asarray(speculative_generate_batched(
            model, params, draft, draft_params, prompt, NEW, n_draft=4))
        solo = np.asarray(speculative_generate_batched(
            model, params, draft, draft_params, newcomer[None, :], NEW,
            n_draft=4))[0]

        bat = ContinuousBatcher(model, draft, params, draft_params,
                                total_len=8 + NEW, n_draft=4)
        bat.start(prompt)
        for _ in range(2):
            bat.step()  # both rows now mid-decode
        assert not bat.all_done
        bat.retire(0)  # preempt row 0...
        bat.admit(0, newcomer)  # ...and admit the newcomer mid-batch
        steps = 0
        while not bat.all_done:
            bat.step()
            steps += 1
            assert steps < 100
        row0, _ = bat.row_tokens(0)
        row1, _ = bat.row_tokens(1)
        np.testing.assert_array_equal(row0, solo)
        np.testing.assert_array_equal(row1, baseline[1])

    def test_validation(self, devices):
        model, params, draft, draft_params = self._models()
        with pytest.raises(ValueError, match="max_seq"):
            ContinuousBatcher(model, draft, params, draft_params,
                              total_len=64, n_draft=4)  # 64 + 4 > 64
        with pytest.raises(ValueError, match="n_draft"):
            ContinuousBatcher(model, draft, params, draft_params,
                              total_len=32, n_draft=0)
        with pytest.raises(ValueError, match="temperature"):
            ContinuousBatcher(model, draft, params, draft_params,
                              total_len=32, sampled=True, temperature=0.0)
        bat = ContinuousBatcher(model, draft, params, draft_params,
                                total_len=16)
        with pytest.raises(ValueError, match="start"):
            bat.step()
        with pytest.raises(ValueError, match="prompt length"):
            bat.start(jnp.zeros((2, 16), jnp.int32))


class TestHostLoopSatellites:
    def test_rolling_cache_prompt_longer_than_slack(self, devices):
        """The host speculative loop prefills through
        ``_chunked_prefill`` now: a rolling-cache model with a prompt
        longer than its decode slack must decode (and still match
        greedy generate) instead of dying in the chunk-size check."""
        model, params = _lm(
            attention_window=8, decode_rolling_cache=True,
            decode_rolling_slack=8,
        )
        P, T = 24, 8  # P >> slack: the old single-shot prefill raised
        prompt = _prompt(B=1, P=P)
        ref = np.asarray(generate(model, params, prompt, T,
                                  temperature=0.0))
        out = np.asarray(speculative_generate(
            model, params, model, params, prompt, T, n_draft=4))
        np.testing.assert_array_equal(out, ref)

    def test_accepted_stat_clamped_by_eos_truncation(self, devices):
        """Self-draft accepts every draft; an eos landing mid-block
        truncates what is EMITTED, and the accepted stat must count the
        emitted drafts, not the pre-truncation acceptance length."""
        model, params = _lm()
        prompt = _prompt(B=1, seed=0)
        ref = np.asarray(generate(model, params, prompt, 12,
                                  temperature=0.0))[0]
        g, second = int(ref[8]), int(ref[9])
        if g == second:
            pytest.skip("degenerate greedy chain: g == second token")
        out, stats = speculative_generate(
            model, params, model, params, prompt, 12, n_draft=4,
            return_stats=True, eos_token=second,
        )
        # round 1: drafts [d1..d4] all accepted, but eos == d1 cuts the
        # emission to one token — accepted must clamp to 1
        assert stats["rounds"] == 1
        assert stats["drafted"] == 4
        assert stats["accepted"] == 1
        row = np.asarray(out)[0]
        assert int(row[9]) == second
        assert np.all(row[10:] == second)  # fixed-length eos fill


class TestDecodeKernelInTheBatcher:
    """ISSUE 31: the round with the decode kernel forced on (the backend
    test patched, the kernel in interpret mode) serves what the
    ``dot_attention`` round serves, and the counters say how much of the
    cache a round has to read."""

    @staticmethod
    def _lm128(seed, attention):
        # heads of 128: the width the kernel takes
        cfg = TransformerConfig(vocab_size=64, hidden=256, n_layers=2,
                                n_heads=2, max_seq=72, attention=attention)
        model = TransformerLM(cfg)
        params = nn.meta.unbox(model.init(
            jax.random.PRNGKey(seed),
            {"tokens": jnp.zeros((1, 8), jnp.int32)})["params"])
        return model, params

    def _serve(self, attention):
        """Three rows; row 0 retired after two rounds, idle for one, then
        a newcomer admitted into it; every row's tokens at the end."""
        model, params = self._lm128(1, attention)
        draft, draft_params = self._lm128(7, attention)
        bat = ContinuousBatcher(model, draft, params, draft_params,
                                total_len=8 + 12, n_draft=4)
        bat.start(_prompt(B=3))
        for _ in range(2):
            bat.step()
        bat.retire(0)
        bat.step()                       # row 0 stands idle
        bat.admit(0, _prompt(B=1, seed=99)[0])
        steps = 0
        while not bat.all_done:
            bat.step()
            steps += 1
            assert steps < 100
        return [np.asarray(bat.row_tokens(r)[0]) for r in range(3)]

    def test_kernel_round_serves_what_the_dot_round_serves(
            self, devices, request):
        from rocket_tpu.observe import trace

        # attention="dot" is a refusal the rule counts: the reference path
        want = self._serve("dot")
        request.getfixturevalue("decode_kernel_here")
        tracer = trace.arm(4096)
        tracer.clear()
        try:
            got = self._serve("auto")
            names = [e[1] for e in tracer.events()]
        finally:
            trace.disarm()
        assert "attention/decode/kernel" in names
        assert "attention/decode/fallback" not in names
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)

    def test_attended_block_share_by_hand(self):
        """Two hand-made rounds: blocks of 512 over 4,100 slots (9 a
        row).  Round 1: rows holding 1, 512, 513 and 4,096 tokens, the
        last finished -> 1 + 1 + 2 of 36.  Round 2: 600 and 2,049 in use,
        two finished -> 2 + 5 of 36."""
        from rocket_tpu.serve.metrics import ServeCounters

        c = ServeCounters()
        c.observe_blocks(np.array([1, 512, 513, 4096]),
                         np.array([False, False, False, True]), 4100, 512)
        assert (c.attended_blocks, c.total_blocks) == (4, 36)
        c.observe_blocks(np.array([600, 7, 2049, 4096]),
                         np.array([False, True, False, True]), 4100, 512)
        assert (c.attended_blocks, c.total_blocks) == (11, 72)
        snap = c.snapshot()
        assert snap["attended_block_share"] == pytest.approx(11 / 72)
        assert snap["attended_blocks"] == 11.0 and snap["total_blocks"] == 72.0
        assert ServeCounters().snapshot()["attended_block_share"] == 0.0

    def test_the_share_costs_no_fetch_and_counts_the_kernel_alone(
            self, devices, request):
        """``step`` counts the blocks from the ``n_tok`` and ``done`` it
        reads anyway: two fetches a round with the counters on, as off.
        And only where the round's attention is the kernel, in its block:
        a round of ``dot_attention`` reads every slot and counts nothing."""
        from rocket_tpu.models.generate import HostReads
        from rocket_tpu.serve.metrics import ServeCounters

        def one_round():
            model, params = self._lm128(1, "auto")
            _, draft_params = self._lm128(7, "auto")
            bat = ContinuousBatcher(model, model, params, draft_params,
                                    total_len=24, n_draft=4)
            counters = ServeCounters()
            bat.reads = HostReads(counters=counters)
            bat.start(_prompt(B=3))
            bat.retire(1)
            before = counters.host_fetches
            n_tok, done = bat.step()
            assert counters.host_fetches - before == 2
            return bat, counters, n_tok, done

        bat, counters, _, _ = one_round()              # CPU: dot_attention
        assert bat._slab is None
        assert (counters.attended_blocks, counters.total_blocks) == (0, 0)
        request.getfixturevalue("decode_kernel_here")
        bat, counters, n_tok, done = one_round()
        assert bat._slab == (72, 80)    # the toy slab is one block a row
        assert counters.total_blocks == 3
        assert counters.attended_blocks == int((~done).sum()) == 2
        assert (n_tok <= 72).all()

    def test_an_idle_round_leaves_a_finished_row_what_it_hands_on(
            self, devices, decode_kernel_here):
        """A finished row stands idle through further rounds before it is
        harvested or overwritten: the kernel skips it, and its chunk is
        written at its frontier as ever, so every slot below the frontier
        — all that a handoff's receiver reads before it rewrites — is bit
        for bit what it was, in both models' caches."""
        from rocket_tpu.models.generate import export_kv_row

        model, params = self._lm128(1, "auto")
        draft, draft_params = self._lm128(7, "auto")
        bat = ContinuousBatcher(model, draft, params, draft_params,
                                total_len=8 + 12, n_draft=4)
        bat.start(_prompt(B=3))
        bat.step()
        bat.retire(0)
        before = export_kv_row(bat.state, 0)
        front = int(before.n_tok[0]) - 1
        assert front >= 8
        for _ in range(2):
            bat.step()
        after = export_kv_row(bat.state, 0)
        assert int(after.n_tok[0]) - 1 == front and bool(after.done[0])
        np.testing.assert_array_equal(np.asarray(before.buf),
                                      np.asarray(after.buf))
        leaves = lambda h: [  # noqa: E731
            np.asarray(x) for x in jax.tree_util.tree_leaves(
                (h.cache_t, h.cache_d)) if x.ndim == 4]
        assert len(leaves(before)) == 8
        for was, now in zip(leaves(before), leaves(after)):
            np.testing.assert_array_equal(was[:, :front], now[:, :front])
