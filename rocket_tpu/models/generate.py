"""Autoregressive generation — KV-cache decode for the transformer family.

Beyond reference parity (the reference ships no model code at all, SURVEY
§5.7), built the TPU way:

- the KV cache is a flax ``cache`` collection of static ``[B, max_seq]``
  buffers (``models.transformer.Attention._decode_attend``) — no dynamic
  shapes anywhere, so the whole generate loop compiles once;
- prefill is ONE batched forward over the prompt (writes the cache at
  position 0) — or slack-sized chunked forwards when the config uses
  the rolling KV cache (``decode_rolling_cache``) — then a ``lax.scan``
  emits one token per step, the standard compile-once decode loop;
- sampling: greedy (``temperature=0``), temperature softmax, optional
  top-k truncation, all per-step under the scan.

Usage::

    from rocket_tpu.models.generate import generate
    tokens = generate(model, params, prompt, max_new_tokens=64,
                      rng=jax.random.PRNGKey(0), temperature=0.8, top_k=40)
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from rocket_tpu.observe.ledger import get_retrace_ledger, ledger_call
from rocket_tpu.observe.trace import get_tracer

# The batcher's prefill/admit/import edges retrace BY DESIGN — every new
# prompt length is a new signature (the one-dispatch batched paths pad to
# fixed shapes; the round-granular step API deliberately does not pad the
# prefill).  Register them as ledger-exempt so the retrace sentinel never
# fires on legitimate per-prompt compiles; ``generate/spec_round`` is NOT
# exempt — its shapes are fixed after warmup, and an unexpected round
# retrace is exactly the bug the sentinel exists to catch (the serve
# loop's deliberate inline n_draft compiles run under ``expect_compile``).
get_retrace_ledger().exempt(
    "generate/spec_prefill", "generate/spec_admit",
    "generate/spec_import_row", "generate/spec_suffix_prefill",
)


def _truncate_logits(logits: jax.Array, top_k: Optional[int],
                     top_p: Optional[float]) -> jax.Array:
    """Apply top-k / top-p truncation to temperature-scaled ``[..., V]``
    logits (masked entries -> -inf; composable — top-k truncates first,
    the nucleus is taken within what survives)."""
    if top_k is not None:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None:
        # Nucleus: smallest prefix of the sorted distribution with
        # cumulative mass >= top_p.  Sorted-space mask scattered back via
        # argsort-of-argsort (static shapes, no dynamic slicing); one
        # argsort + one gather, not a second value sort.
        order = jnp.flip(jnp.argsort(logits, axis=-1), axis=-1)
        sorted_logits = jnp.take_along_axis(logits, order, axis=-1)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep entries where the mass BEFORE them is < top_p (the first
        # entry always survives)
        keep_sorted = (cum - probs) < top_p
        ranks = jnp.argsort(order, axis=-1)
        keep = jnp.take_along_axis(keep_sorted, ranks, axis=-1)
        logits = jnp.where(keep, logits, -jnp.inf)
    return logits


def _sample(logits: jax.Array, rng: jax.Array, temperature: float,
            top_k: Optional[int], top_p: Optional[float] = None) -> jax.Array:
    """One sampling step on ``[B, V]`` logits (greedy / temperature /
    top-k / top-p nucleus)."""
    if top_p is not None and not 0.0 < top_p <= 1.0:
        # Validate even on the greedy path: a bad top_p must not hide
        # behind the temperature<=0 early return.
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = _truncate_logits(logits.astype(jnp.float32) / temperature,
                              top_k, top_p)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


# Cache leaves that hold a row's recurrent state (a state-space layer's,
# :mod:`rocket_tpu.models.mamba`): a row's is replaced whole, never sliced.
_STATE_LEAVES = frozenset({"ssm_state", "conv_state", "dt_state",
                           "state_pos"})
# Payload leaves stored slots last (the indexer's keys ``[B, index_dim,
# slots]``, the layout a TPU keeps for their score product); every other
# payload leaf holds its slots on axis 1.
_SLOTS_LAST_LEAVES = frozenset({"cached_index_k"})


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def _slot_axis(path) -> int:
    """The axis of a payload leaf's slots."""
    return -1 if _leaf_name(path) in _SLOTS_LAST_LEAVES else 1


def _is_cache_payload(path, leaf) -> bool:
    """Whether the cache leaf at ``path`` holds rows of tokens slot by slot
    — K/V ``[B, slots, KV, D]``, their int8 scales ``[B, slots, KV, 1]``, a
    latent ``[B, slots, C]``, the indexer's keys ``[B, index_dim, slots]``
    (:func:`_slot_axis`) — as against the scalar
    ``cache_index`` and a row's recurrent state: what row scatters,
    exports, pages and beam gathers slice.  A state is told by its name
    (``_STATE_LEAVES``): its ``[B, heads, P, N]`` has K's rank."""
    return getattr(leaf, "ndim", 0) >= 3 and not _is_row_state(path)


def _is_row_state(path) -> bool:
    return _leaf_name(path) in _STATE_LEAVES


def _keeps_state(model: Any) -> bool:
    """Whether ``model`` has state-space layers (``config.mamba``): what
    handoffs, pages, the prefix store, beam search, the host loops and a
    hidden-state draft cannot move or rewind yet."""
    return bool(getattr(model.config, "keeps_state", False))


def _commit_kw(model: Any, n: int) -> dict:
    """``commit=n`` for a model with state-space layers (how many of a
    decode pass's tokens their state takes in); nothing for any other."""
    return {"commit": n} if _keeps_state(model) else {}


def _pending_kw(model: Any, n_draft: int) -> dict:
    """For a model with state-space layers, its config's ``mamba`` with a
    cache that holds a round's ``n_draft`` unconfirmed tokens pending;
    nothing for any other."""
    if not _keeps_state(model):
        return {}
    return {"mamba": dataclasses.replace(model.config.mamba,
                                         pending=int(n_draft))}


def _latent(model: Any) -> bool:
    """Whether ``model`` caches latents (``config.mla``): what handoffs,
    pages, the prefix store and beam search cannot move yet."""
    return getattr(model.config, "mla", None) is not None


def _prefill_kw(model: Any) -> dict:
    """``prefill=True`` for a model whose attention takes a path of its
    own into an empty cache (latent attention expands the prompt's keys
    where a decode round scores the cached latents)."""
    return {"prefill": True} if _latent(model) else {}


def decode_cache_shapes(model: Any, params: Any, prompt: jax.Array,
                        extra: Optional[dict] = None):
    """Static KV-cache shapes/dtypes for decoding ``prompt`` with ``params``.

    Shapes derive from the CALLER's params (not a fresh f32 init): the
    cache variables take their dtype from the computed k/v, so decoding
    with bf16-cast weights needs a bf16 cache — a fresh init would make
    an f32 one and ``dynamic_update_slice`` rejects the dtype mismatch.
    eval_shape costs nothing at runtime.  ``extra`` is what
    else the model's batch holds (a hidden-state draft is given the
    target's hidden states, embedding and head, and its cache takes their
    type)."""
    return jax.eval_shape(
        lambda p: model.apply(
            {"params": p}, {"tokens": prompt, **(extra or {})}, decode=True,
            mutable=["cache"],
        )[1]["cache"],
        params,
    )


def zero_cache(model: Any, params: Any, prompt: jax.Array,
               extra: Optional[dict] = None) -> Any:
    """A fresh all-zeros KV cache shaped by :func:`decode_cache_shapes`."""
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        decode_cache_shapes(model, params, prompt, extra),
    )


def _admission_chunk(model: Any) -> Optional[int]:
    """Queries of a prompt a model asks to be admitted at a time
    (``config.select.chunk``), or ``None``: the whole prompt at once."""
    select = getattr(model.config, "select", None)
    return None if select is None else int(select.chunk)


def _prefill_in_chunks(model, params, cache, prompt, chunk):
    """The prompt through the decode path ``chunk`` queries at a time,
    inside ONE compiled loop over the whole chunks (the cache is the
    loop's carry and is written where it lies) and one more pass for a
    ragged rest: a prompt of 16,384 tokens is 32 turns of one body, not 32
    bodies, and the scores alive at once are one chunk's."""
    B, P = prompt.shape
    n_full, rest = divmod(P, chunk)

    def piece(cache, toks, c0):
        pos = jnp.broadcast_to(
            c0 + jnp.arange(toks.shape[1], dtype=jnp.int32),
            (B, toks.shape[1]))
        out, mutated = model.apply(
            {"params": params, "cache": cache},
            {"tokens": toks, "positions": pos},
            decode=True, mutable=["cache"], **_prefill_kw(model),
        )
        return mutated["cache"], out["logits"][:, -1].astype(jnp.float32)

    def turn(i, carry):
        c0 = i * chunk
        toks = jax.lax.dynamic_slice_in_dim(prompt, c0, chunk, axis=1)
        return piece(carry[0], toks, c0)

    last = jnp.zeros((B, model.config.vocab_size), jnp.float32)
    cache, last = jax.lax.fori_loop(0, n_full, turn, (cache, last))
    if rest:
        cache, last = piece(cache, prompt[:, n_full * chunk:],
                            jnp.int32(n_full * chunk))
    return cache, last


def _chunked_prefill(model, params, cache, prompt):
    """Run the prompt through the decode path and return
    ``(cache, last-position f32 logits)``.

    One forward for a plain cache; slack-sized chunks for a rolling
    cache (``decode_rolling_cache``) — a single chunk's writes must not
    clobber keys still inside a live query's window, and only the final
    chunk's last-position logits matter to any caller.  A model that
    declares an admission chunk (:func:`_admission_chunk`) runs a longer
    prompt in chunks of that many queries inside one compiled loop
    (:func:`_prefill_in_chunks`)."""
    B, P = prompt.shape
    chunk = _admission_chunk(model)
    if chunk is not None and P > chunk:
        return _prefill_in_chunks(model, params, cache, prompt, chunk)
    step_len = (
        model.config.decode_rolling_slack
        if getattr(model.config, "decode_rolling_cache", False) else P
    )
    out = None
    for c0 in range(0, P, step_len):
        piece = prompt[:, c0:c0 + step_len]
        pos = jnp.broadcast_to(
            jnp.arange(c0, c0 + piece.shape[1], dtype=jnp.int32),
            (B, piece.shape[1]),
        )
        out, mutated = model.apply(
            {"params": params, "cache": cache},
            {"tokens": piece, "positions": pos},
            decode=True, mutable=["cache"], **_prefill_kw(model),
        )
        cache = mutated["cache"]
    return cache, out["logits"][:, -1].astype(jnp.float32)


def _row_prefill(model, params, prompt_row):
    """One request's prefill at batch 1 from an empty cache, for an
    admission: ``(cache, last logits)``.  A model that admits in chunks
    prefills into a cache of the prompt's own length (its chunks then score
    the slab's prefix, not the slots no prompt token can see);
    :func:`_scatter_row` puts it at the head of the row's slab."""
    if _admission_chunk(model) is not None:
        model = model.clone(config=dataclasses.replace(
            model.config, max_seq=int(prompt_row.shape[1])))
    return _chunked_prefill(
        model, params, zero_cache(model, params, prompt_row), prompt_row)


def generate(
    model: Any,
    params: Any,
    prompt: jax.Array,
    max_new_tokens: int,
    rng: Optional[jax.Array] = None,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    eos_token: Optional[int] = None,
) -> jax.Array:
    """Generate ``max_new_tokens`` continuations of ``prompt`` (``[B, P]``
    int32) with a KV cache; returns ``[B, P + max_new_tokens]`` tokens.

    ``model`` is a :class:`~rocket_tpu.models.transformer.TransformerLM`
    whose config uses the unrolled layer layout (``scan_layers=False``,
    ``remat=False``, no pipeline).  ``P + max_new_tokens`` must fit in
    ``config.max_seq``.  Wrap in ``jax.jit`` (static
    ``max_new_tokens``/``temperature``/``top_k``) for repeated use.

    ``eos_token``: rows that emit it keep repeating it for the rest of
    the fixed-length output (shapes stay static under jit — trim on the
    host). Sampling randomness is consumed identically either way, so
    the pre-EOS prefix matches the no-eos call bit for bit.
    """
    cfg = model.config
    B, P = prompt.shape
    if max_new_tokens < 1:
        # scan(length=max_new_tokens-1) would die on a negative length
        # far from the caller's mistake — and 0 would still emit the
        # prefill sample; fail loudly instead
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    total = P + max_new_tokens
    if total > cfg.max_seq:
        raise ValueError(
            f"prompt ({P}) + max_new_tokens ({max_new_tokens}) = {total} "
            f"exceeds config.max_seq ({cfg.max_seq})"
        )
    if rng is None:
        rng = jax.random.PRNGKey(0)

    cache, last = _chunked_prefill(
        model, params, zero_cache(model, params, prompt), prompt
    )
    rng, sub = jax.random.split(rng)
    tok = _sample(last, sub, temperature, top_k, top_p)
    done = jnp.zeros((B,), bool) if eos_token is None else tok == eos_token
    if eos_token is not None:
        eos = jnp.asarray(eos_token, jnp.int32)

    def step(carry, _):
        cache, tok, rng, pos, done = carry
        batch = {
            "tokens": tok[:, None],
            "positions": jnp.broadcast_to(pos[None, None], (B, 1)),
        }
        out, mutated = model.apply(
            {"params": params, "cache": cache}, batch,
            decode=True, mutable=["cache"],
        )
        rng, sub = jax.random.split(rng)
        nxt = _sample(out["logits"][:, 0], sub, temperature, top_k, top_p)
        if eos_token is not None:
            nxt = jnp.where(done, eos, nxt)
            done = done | (nxt == eos)
        return (mutated["cache"], nxt, rng, pos + 1, done), tok

    init = (cache, tok, rng, jnp.asarray(P, jnp.int32), done)
    (cache, tok, rng, _, done), toks = jax.lax.scan(
        step, init, None, length=max_new_tokens - 1
    )
    # toks holds tokens emitted at steps 0..max_new-2; the final carry tok
    # is the last one
    generated = jnp.concatenate(
        [toks.swapaxes(0, 1), tok[:, None]], axis=1
    )
    return jnp.concatenate([prompt, generated], axis=1)



def _set_cache_index(cache: Any, value) -> Any:
    """Rewind every layer's ``cache_index`` to ``value``.

    Stale K/V entries beyond the new index are harmless: the causal mask
    keeps queries from attending past their own position, and the next
    ``dynamic_update_slice`` writes overwrite the stale slots in place.
    """
    from collections.abc import Mapping

    val = jnp.asarray(value, jnp.int32)
    hits = 0

    def walk(node):
        nonlocal hits
        if isinstance(node, Mapping):  # dict OR FrozenDict
            out = {}
            for k, v in node.items():
                if k == "cache_index":
                    hits += 1
                    out[k] = val
                else:
                    out[k] = walk(v)
            return out
        return node

    rewound = walk(cache)
    if hits == 0:
        raise ValueError(
            "no cache_index leaves found — not a decode cache tree? "
            "(a silent no-op here would corrupt the KV frontier)"
        )
    return rewound


@functools.partial(jax.jit, static_argnums=0)
def _prefill_cache(model, params, prompt):
    """Jitted prompt prefill from a zero cache for the HOST loops:
    ``(cache, last-position f32 logits [B, V])`` via
    :func:`_chunked_prefill`, so rolling-cache models chunk by their
    slack instead of dying in ``_decode_attend``'s chunk-size check on
    long prompts (the batched path already prefills this way)."""
    return _chunked_prefill(
        model, params, zero_cache(model, params, prompt), prompt
    )


@functools.partial(jax.jit, static_argnums=0)
def _chunk_step(model, params, cache, toks, pos0):
    """Apply ``toks`` ([1, S]) at positions pos0..pos0+S-1; returns
    (cache, greedy next-token per position [1, S]).

    Module-level jit with the (hashable) flax module static and params
    traced: the compiled executables persist across
    :func:`speculative_generate` calls — a serving loop pays compilation
    once per (model, shape), not per request."""
    S = toks.shape[1]
    positions = pos0 + jnp.arange(S, dtype=jnp.int32)[None, :]
    out, mutated = model.apply(
        {"params": params, "cache": cache},
        {"tokens": toks, "positions": positions},
        decode=True, mutable=["cache"],
    )
    return mutated["cache"], jnp.argmax(out["logits"], axis=-1)


def _speculative_loop(
    caller: str,
    model: Any,
    draft_model: Any,
    prompt: jax.Array,
    max_new_tokens: int,
    n_draft: int,
    return_stats: bool,
    eos_token: Optional[int],
    prefill,
    do_round,
    rewind,
):
    """Shared round loop for both speculative variants.

    Owns everything variant-independent: validation, the token list and
    frontier arithmetic (``pos`` = target frontier = ``len(tokens) - 1``,
    the pending token is always ``tokens[-1]``; the draft frontier ends a
    round at ``pos + k`` and is clamped to the accepted prefix), the
    fixed-length eos contract, truncation, and stats.  The variants
    supply ``prefill() -> g``, ``do_round(feed, feed_start, pending,
    pos, k) -> (drafts, extra_token, j)`` (drafting, the single target
    verification forward, and the accept rule), and ``rewind(pos,
    d_pos)`` (cache-index rewinds — the caches live in the variant's
    closure).
    """
    B, P = prompt.shape
    if B != 1:
        raise ValueError(
            f"{caller} requires batch=1 (got {B}): acceptance length is "
            f"data-dependent per row"
        )
    if _keeps_state(model) or _keeps_state(draft_model):
        raise ValueError(
            f"{caller} cannot run state-space layers (mamba) yet: it rewinds "
            f"caches by their cache_index, which a recurrent state cannot "
            f"follow (speculative_generate_batched and ContinuousBatcher "
            f"hold the unaccepted tokens pending instead)")
    if n_draft < 1:
        raise ValueError(f"{caller} needs n_draft >= 1, got {n_draft}")
    total = P + max_new_tokens
    if total > model.config.max_seq or total > draft_model.config.max_seq:
        raise ValueError(
            f"prompt ({P}) + max_new_tokens ({max_new_tokens}) = {total} "
            f"exceeds a model's max_seq"
        )
    if max_new_tokens <= 0:
        # same contract as generate() — a silent bare-prompt return here
        # would break the documented exact-match relationship (ADVICE r4)
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")

    g = prefill()

    # all known-correct tokens; the LAST one is always the pending token
    # (not yet processed by either model)
    tokens = list(np.asarray(prompt[0])) + [g]
    n_out = 1
    stats = {"rounds": 0, "drafted": 0, "accepted": 0}
    if eos_token is not None and g == eos_token:
        # the very first token finished the row: emit the frozen all-eos
        # tail (same fixed-length contract as generate())
        tokens.extend([eos_token] * (max_new_tokens - 1))
        n_out = max_new_tokens
    d_pos = P    # draft frontier — may trail pos by one fully-accepted
    # draft d_k the draft proposed but never processed: the catch-up
    # feed (tokens[d_pos:]) covers it next round; skipping it would
    # leave an unwritten KV slot every later draft step attends to,
    # silently collapsing the acceptance rate
    while n_out < max_new_tokens:
        pos = len(tokens) - 1  # target frontier: slots [0, pos) valid
        k = min(n_draft, max_new_tokens - n_out)
        drafts, tok, j = do_round(tokens[d_pos:], d_pos, tokens[-1], pos, k)
        d_pos = pos + k  # draft processed ...d_{k-1}, only PROPOSED d_k
        # accept d_1..d_j plus the round's extra token (greedy: the
        # target's own next token; sampling: the resample/bonus draw)
        new_toks = (drafts[:j] + [tok])[: max_new_tokens - n_out]
        finished = eos_token is not None and eos_token in new_toks
        if finished:
            # freeze at eos exactly like generate(): keep the prefix
            # through the first eos, fill the rest of the fixed-length
            # output with eos, and stop decoding
            new_toks = new_toks[: new_toks.index(eos_token) + 1]
        stats["rounds"] += 1
        stats["drafted"] += k
        # accepted counts drafts actually EMITTED, matching the batched
        # path (min(j, acc) there): an eos/budget-truncated round must
        # not inflate the acceptance rate
        stats["accepted"] += min(j, len(new_toks))
        tokens.extend(new_toks)
        n_out += len(new_toks)
        if finished:
            tokens.extend([eos_token] * (max_new_tokens - n_out))
            break
        d_pos = min(d_pos, len(tokens) - 1)
        rewind(len(tokens) - 1, d_pos)

    out = jnp.asarray(tokens, jnp.int32)[None, :]
    return (out, stats) if return_stats else out


def speculative_generate(
    model: Any,
    params: Any,
    draft_model: Any,
    draft_params: Any,
    prompt: jax.Array,
    max_new_tokens: int,
    n_draft: int = 4,
    return_stats: bool = False,
    eos_token: Optional[int] = None,
) -> Any:
    """Greedy speculative decoding: a small draft model proposes
    ``n_draft`` tokens per round and the target verifies the whole block
    in ONE forward — the output is EXACTLY ``generate(model, params,
    prompt, ..., temperature=0.0)``, but the target's weights are read
    once per accepted block instead of once per token.  Decode is
    bandwidth-bound (the serving cells' ``decode_round_roofline.*`` is
    bound by bytes), so accepted blocks of ``j`` tokens cut the
    dominant HBM term by ``~j×``.

    Batch size must be 1 (acceptance length is data-dependent per row,
    and the KV caches keep one scalar frontier).  Both models must share
    the vocabulary.  The loop is host-driven — each jitted piece has a
    static shape; wrap-and-reuse happens naturally in a serving process.
    The reference has no generation path at all (SURVEY §2).

    Returns ``[1, P + max_new_tokens]`` tokens — or, with
    ``return_stats=True``, a ``(tokens, stats)`` tuple where ``stats``
    counts ``rounds`` / ``drafted`` / ``accepted`` (acceptance rate is
    the whole bandwidth win; a perfect draft accepts everything).

    ``eos_token`` matches :func:`generate`'s fixed-length contract: the
    output keeps the prefix through the first eos and fills the rest
    with eos (decoding stops early — that, not shape, is the saving).
    """
    target_step = functools.partial(_chunk_step, model, params)
    draft_step = functools.partial(_chunk_step, draft_model, draft_params)
    caches = {}

    def prefill():
        # the target's last-position argmax is the first pending token g;
        # _prefill_cache chunks rolling-cache prompts by their slack
        caches["t"], last = _prefill_cache(model, params, prompt)
        caches["d"], _ = _prefill_cache(draft_model, draft_params, prompt)
        return int(np.asarray(jnp.argmax(last[0])))

    def do_round(feed_toks, feed_start, pending, pos, k):
        feed = jnp.asarray(feed_toks, jnp.int32)[None, :]
        caches["d"], nxt = draft_step(caches["d"], feed, feed_start)
        dp = feed_start + len(feed_toks)
        d_toks = [int(np.asarray(nxt[0, -1]))]
        for _ in range(k - 1):
            caches["d"], nxt = draft_step(
                caches["d"], jnp.asarray([[d_toks[-1]]], jnp.int32), dp
            )
            dp += 1
            d_toks.append(int(np.asarray(nxt[0, -1])))

        # ONE target forward over [g, d_1..d_k]: position i's argmax is
        # the target's greedy token AFTER seeing chunk[:i+1]
        chunk = jnp.asarray([[pending] + d_toks], jnp.int32)
        caches["t"], t_next = target_step(caches["t"], chunk, pos)
        y_np = np.asarray(t_next[0])
        j = 0
        while j < k and d_toks[j] == y_np[j]:
            j += 1
        return d_toks, int(y_np[j]), j

    def rewind(pos, d_pos):
        caches["t"] = _set_cache_index(caches["t"], pos)
        caches["d"] = _set_cache_index(caches["d"], d_pos)

    return _speculative_loop(
        "speculative_generate", model, draft_model, prompt, max_new_tokens,
        n_draft, return_stats, eos_token, prefill, do_round, rewind,
    )


def _accept_resample_rows(p_rows: jax.Array, q_rows: jax.Array,
                          drafts: jax.Array, key: jax.Array):
    """Vectorized speculative-sampling accept/resample (the device-side
    counterpart of :func:`_accept_resample`; same math, one batch at a
    time).  ``p_rows`` ``[B, k+1, V]`` target distributions, ``q_rows``
    ``[B, k, V]`` draft distributions, ``drafts`` ``[B, k]`` proposals.
    Returns ``(j [B], tok [B])``: accepted-prefix length per row and the
    round's final emitted token — a residual resample from
    ``max(0, p - q)`` at the first rejection, or a bonus draw from
    ``p_rows[:, k]`` when everything is accepted.  Emitted tokens are
    distributed exactly per the target ``p`` whatever ``q`` is
    (distributionally tested against the host version)."""
    B, k1, V = p_rows.shape
    k = k1 - 1
    ku, kr = jax.random.split(key)
    u = jax.random.uniform(ku, (B, k), jnp.float32)
    p_d = jnp.take_along_axis(p_rows[:, :k], drafts[..., None], -1)[..., 0]
    q_d = jnp.take_along_axis(q_rows, drafts[..., None], -1)[..., 0]
    # accept d_i iff u < min(1, p/q)  <=>  u * q < p (q > 0 for a token
    # that was actually sampled from q; numeric zero -> reject)
    accept = (q_d > 0.0) & (u * q_d < p_d)
    j = jnp.cumprod(accept.astype(jnp.int32), axis=1).sum(axis=1)  # [B]
    p_j = jnp.take_along_axis(p_rows, j[:, None, None], 1)[:, 0]   # [B, V]
    q_pad = jnp.concatenate(  # row j==k pairs with q=0 -> residual = p_k
        [q_rows, jnp.zeros((B, 1, V), q_rows.dtype)], axis=1)
    q_j = jnp.take_along_axis(q_pad, j[:, None, None], 1)[:, 0]
    residual = jnp.clip(p_j - q_j, 0.0, None)
    total = residual.sum(-1, keepdims=True)
    probs = jnp.where(total > 0.0, residual, p_j)  # degenerate: back to p
    tok = jax.random.categorical(kr, jnp.log(probs), axis=-1)
    return j, tok.astype(jnp.int32)


def _scatter_row(batch_cache: Any, one_cache: Any, row) -> Any:
    """Put a batch-1 cache into row ``row`` of a batch cache: payload
    leaves (K/V ``[B, slots, KV, D]``, int8 scales, a latent ``[B, slots,
    C]``, the indexer's keys ``[B, index_dim, slots]``) take the fresh
    row; a recurrent state's leaves replace the row's
    whole (a state is not masked by position: what the previous occupant
    left must not survive); the scalar ``cache_index`` is bookkeeping
    only under per-row frontiers — kept monotone so rolling-cache chunk
    math stays conservative."""
    def put(path, a, b):
        if _is_row_state(path):
            return a.at[row].set(b[0])
        if not _is_cache_payload(path, a):
            return jnp.maximum(a, b)
        ax = _slot_axis(path)
        if b.shape[ax] == a.shape[ax]:
            return a.at[row].set(b[0])
        # a prefill into a cache of the prompt's own length
        # (:func:`_row_prefill`): the head of the row's slab; what the
        # previous occupant left past it is hidden causally like the rest
        return jax.lax.dynamic_update_slice(
            a, b, (row,) + (0,) * (a.ndim - 1))

    return jax.tree_util.tree_map_with_path(put, batch_cache, one_cache)


def _spec_prefill_impl(model, draft_model, params, draft_params, prompt,
                       key, temperature, *, max_new_tokens, eos_token,
                       sampled, top_k, top_p):
    """Build the speculative round-loop carry state: both prompt
    prefills plus the first emitted token g.  Returns the state tuple
    ``(buf, n_tok, done, cache_t, cache_d, key, (rounds, drafted,
    accepted))`` threaded through :func:`_spec_round_impl` — every leaf
    stays on device, so a host driver holding the state between rounds
    pays no transfers."""
    B, P = prompt.shape
    total = P + max_new_tokens
    if key is None:
        key = jax.random.PRNGKey(0)

    # prefill both models over the prompt (uniform frontiers: all rows
    # 0); a rolling-cache model prefills in slack-sized chunks
    cache_t, last = _chunked_prefill(
        model, params, zero_cache(model, params, prompt), prompt
    )
    cache_d, _ = _chunked_prefill(
        draft_model, draft_params,
        zero_cache(draft_model, draft_params, prompt), prompt
    )
    if sampled:
        key, kg = jax.random.split(key)
        g = jax.random.categorical(
            kg, _truncate_logits(last / temperature, top_k, top_p),
            axis=-1,
        ).astype(jnp.int32)
    else:
        g = jnp.argmax(last, axis=-1).astype(jnp.int32)

    buf = jnp.zeros((B, total), jnp.int32)
    buf = jax.lax.dynamic_update_slice(buf, prompt, (0, 0))
    buf = buf.at[:, P].set(g)
    n_tok = jnp.full((B,), P + 1, jnp.int32)
    done = (g == eos_token) if eos_token is not None \
        else jnp.zeros((B,), bool)
    stats0 = (jnp.zeros((), jnp.int32),      # rounds
              jnp.zeros((B,), jnp.int32),    # drafted per row
              jnp.zeros((B,), jnp.int32))    # accepted per row
    state = (buf, n_tok, done, cache_t, cache_d, key, stats0)
    if _round_counts(model, draft_model):
        # an eighth entry, only for models with experts or a selection:
        # every other model's state, and so its compiled round, is as it was
        state += (_zero_counters(model, draft_model),)
    return state


def _spec_round_impl(model, draft_model, params, draft_params, state,
                     temperature, *, n_draft, eos_token, sampled, top_k,
                     top_p):
    """ONE speculative decode round: the fused draft chain, the single
    target verification forward, accept/emit, and stats — the body of
    :func:`_spec_batched_run`'s while_loop AND the unit of the step API
    (:class:`ContinuousBatcher` runs it once per call so requests can
    join between rounds).  ``state`` is a :func:`_spec_prefill_impl`
    tuple; batch size and buffer length derive from ``buf``'s shape.

    Why no cache rewinds: with per-row positions, a stale K/V slot past
    a row's frontier has a key position larger than every live query
    position, so the causal mask hides it; the next round's chunk
    (which always spans at least as far) overwrites it in place before
    anything can attend to it.  The same masking argument admits a NEW
    request into a retired row mid-batch (:func:`_spec_admit`): the old
    request's leftover K/V beyond the fresh prompt are invisible to it.

    A recurrent state has no positions to mask by, so a model with
    state-space layers commits only what is certain (the chunk's first
    token, always accepted) and holds the rest of the chunk's inputs
    pending; its next pass applies the ones its frontier says were
    accepted first (:mod:`rocket_tpu.models.mamba`).  The draft's chain
    commits its first step alone (the step index is the traced ``commit``)
    and holds its later ones pending the same way, so both models' states
    end each round after exactly the ``j + 1`` tokens the row accepted.
    """
    (buf, n_tok, done_in, cache_t, cache_d, key_in,
     (rounds, drafted, accepted)) = state[:7]
    # the device counters of a model with experts or a selection
    # (:func:`_zero_counters`); both models then sow what they routed and
    # chose, and the round adds it up over its live rows
    counters = state[7] if len(state) > 7 else None
    mutable = ["cache"] if counters is None \
        else ["cache", "routing", "selection"]
    B, total = buf.shape
    k = n_draft
    ar = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
    key_draft, key_accept, key_out = jax.random.split(key_in, 3)
    pos = n_tok - 1                                     # [B] frontiers
    pending = jnp.take_along_axis(buf, pos[:, None], axis=1)[:, 0]
    # Both models are told which rows are finished (``"idle"``): what such
    # a row emits is dropped below (``keep``), so its attention may read
    # nothing (``Attention._decode_attend``) and a round's attention
    # follows the rows in use.  Its chunk is written at its frontier as
    # ever: no slot below it changes while the row waits to be harvested.

    # Draft chain, fused: k+1 single-token steps under ONE scan.
    # Step i processes chunk token C_i at position pos+i and proposes
    # C_{i+1}; the extra (k+1)-th step exists so the draft cache
    # always covers the whole chunk — no catch-up feed next round.
    def draft_step(carry, xs):
        cache_d, tok = carry
        i, ki = xs
        # a state-space draft commits its first step (the row's pending
        # token, always accepted) and holds the later ones pending until
        # the round knows how many of them it accepted
        out, mut = draft_model.apply(
            {"params": draft_params, "cache": cache_d},
            {"tokens": tok[:, None], "positions": (pos + i)[:, None],
             "idle": done_in},
            decode=True, mutable=mutable,
            **({"commit": (i == 0).astype(jnp.int32)}
               if _keeps_state(draft_model) else {}),
        )
        logits = out["logits"][:, 0].astype(jnp.float32)
        if sampled:
            # truncated-renormalized q: the accept/resample theorem
            # holds for ANY q as long as p and q are the actual
            # proposal/verify distributions — truncating both makes
            # the emitted tokens exactly truncated-target-distributed
            logits = _truncate_logits(logits / temperature, top_k, top_p)
            nxt = jax.random.categorical(
                ki, logits, axis=-1).astype(jnp.int32)
            q_row = jax.nn.softmax(logits, axis=-1)
        else:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            q_row = jnp.zeros((B, 0), jnp.float32)  # unused
        if counters is None:
            return (mut["cache"], nxt), (tok, q_row)
        return (mut["cache"], nxt), (tok, q_row, _count_pass(
            draft_model, mut, ~done_in, counters))

    (cache_d, _), (chunk_t, q_t, *d_count) = jax.lax.scan(
        draft_step, (cache_d, pending),
        (jnp.arange(k + 1, dtype=jnp.int32),
         jax.random.split(key_draft, k + 1)),
    )
    chunk = chunk_t.swapaxes(0, 1)        # [B, k+1]: [pending, d_1..d_k]
    drafts = chunk[:, 1:]                 # [B, k]

    # ONE target forward verifies every row's whole chunk
    out, mut = model.apply(
        {"params": params, "cache": cache_t},
        {"tokens": chunk, "positions": pos[:, None] + ar, "idle": done_in},
        decode=True, mutable=mutable, **_commit_kw(model, 1),
    )
    cache_t = mut["cache"]
    t_logits = out["logits"].astype(jnp.float32)        # [B, k+1, V]

    if sampled:
        # rejection sampling: accept d_i with prob min(1, p/q); the
        # emitted tokens are the accepted DRAFTS plus the round's
        # resample/bonus draw
        p_rows = jax.nn.softmax(
            _truncate_logits(t_logits / temperature, top_k, top_p),
            axis=-1,
        )
        q_rows = q_t[:k].swapaxes(0, 1)                 # [B, k, V]
        j, tok = _accept_resample_rows(
            p_rows, q_rows, drafts, key_accept)
        vals = jnp.where(
            ar < j[:, None],
            jnp.concatenate([drafts, drafts[:, -1:]], axis=1),
            tok[:, None],
        )
    else:
        # greedy: leading draft/argmax agreement; the accepted drafts
        # ARE the target's own argmaxes, so each row's new tokens are
        # simply y[:, :j+1] (bonus/correction token included)
        y = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)
        match = (drafts == y[:, :k]).astype(jnp.int32)
        j = jnp.cumprod(match, axis=1).sum(axis=1)      # [B], 0..k
        vals = y

    keep = ar <= j[:, None]
    if eos_token is not None:
        # freeze at the first emitted eos: keep through it, drop after
        no_eos_before = jnp.cumprod(jnp.concatenate(
            [jnp.ones((B, 1), jnp.int32),
             (vals[:, :k] != eos_token).astype(jnp.int32)], axis=1,
        ), axis=1).astype(bool)
        keep = keep & no_eos_before
    keep = keep & ((n_tok[:, None] + ar) < total) & ~done_in[:, None]

    cols = jnp.where(keep, n_tok[:, None] + ar, total)  # OOB -> dropped
    rows = jnp.broadcast_to(jnp.arange(B)[:, None], cols.shape)
    buf = buf.at[rows, cols].set(vals, mode="drop")

    acc = keep.sum(axis=1).astype(jnp.int32)
    n_tok = n_tok + acc
    done = done_in | (n_tok >= total)
    if eos_token is not None:
        done = done | jnp.any((vals == eos_token) & keep, axis=1)
    active = ~done_in
    # Stats mirror the host loop's semantics: drafted clamps to the
    # row's remaining token budget (the B=1 loop shortens its last
    # draft chain the same way), and accepted counts drafts actually
    # EMITTED — of the acc written tokens the first min(j, acc) are
    # draft proposals, the rest is the bonus/correction token.  A
    # total-cap or eos truncation must not inflate the rate.
    remaining = total - (n_tok - acc)  # budget at round START
    stats = (rounds + 1,
             drafted + jnp.where(active, jnp.minimum(k, remaining), 0),
             accepted + jnp.where(active, jnp.minimum(j, acc), 0))
    out_state = (buf, n_tok, done, cache_t, cache_d, key_out, stats)
    if counters is None:
        return out_state
    # (written out again rather than shared with ``stats`` above: a dense
    # pair's jaxpr keeps the order of operations it always had)
    n_drafted = jnp.where(active, jnp.minimum(k, remaining), 0)
    n_accepted = jnp.where(active, jnp.minimum(j, acc), 0)
    # the draft chain's steps (a leading axis from the scan) summed; its
    # experts' rows follow the target's, as in a hidden-state draft's round
    d_count = jax.tree_util.tree_map(lambda x: jnp.sum(x, axis=0),
                                     d_count[0])
    return out_state + (_add_counts(
        counters, _count_pass(model, mut, active, counters), d_count,
        jnp.sum(n_drafted), jnp.sum(n_accepted)),)


def _spec_eos_fill(buf, n_tok, eos_token):
    """Fixed-length contract: eos-frozen rows fill their tail with eos
    (rows without an eos ended at ``n_tok == total`` — no-op for them)."""
    if eos_token is None:
        return buf
    cols = jnp.arange(buf.shape[1], dtype=jnp.int32)[None, :]
    return jnp.where(cols >= n_tok[:, None], eos_token, buf)


@functools.partial(
    jax.jit, static_argnums=(0, 1),
    static_argnames=("max_new_tokens", "n_draft", "eos_token", "sampled",
                     "top_k"),
)
def _spec_batched_run(model, draft_model, params, draft_params, prompt,
                      key=None, temperature=0.0, *, max_new_tokens,
                      n_draft, eos_token, sampled=False, top_k=None,
                      top_p=None):
    """The device-resident round loop behind
    :func:`speculative_generate_batched` (``sampled=False``: greedy,
    draft-agreement acceptance) and :func:`speculative_sample_batched`
    (``sampled=True``: rejection sampling via
    :func:`_accept_resample_rows`) — one ``lax.while_loop`` over
    :func:`_spec_round_impl`, zero host syncs until the final result.
    ``model``/``draft_model`` must be ``decode_per_row`` variants (rows
    keep independent frontiers).  The prefill/round pieces are shared
    with the step API (:func:`_spec_prefill` / :func:`_spec_round`), so
    the one-dispatch offline path and the round-granular serving path
    cannot drift.

    Static (recompiling) arguments: the boolean mode and ``top_k``
    (a lax.top_k shape).  ``temperature`` and ``top_p`` are traced
    operands, so per-request values reuse one compiled executable
    (top_p's None-ness still splits the cache once).
    """
    state = _spec_prefill_impl(
        model, draft_model, params, draft_params, prompt, key, temperature,
        max_new_tokens=max_new_tokens, eos_token=eos_token, sampled=sampled,
        top_k=top_k, top_p=top_p,
    )

    def cond(state):
        return ~jnp.all(state[2])

    def body(state):
        return _spec_round_impl(
            model, draft_model, params, draft_params, state, temperature,
            n_draft=n_draft, eos_token=eos_token, sampled=sampled,
            top_k=top_k, top_p=top_p,
        )

    out = jax.lax.while_loop(cond, body, state)
    return _spec_eos_fill(out[0], out[1], eos_token), out[6]


def _spec_batched_call(model, draft_model, params, draft_params, prompt,
                       max_new_tokens, n_draft, eos_token, return_stats,
                       key=None, temperature=0.0, sampled=False,
                       top_k=None, top_p=None):
    """Shared front door for both batched speculative wrappers:
    validation (including the max_seq + n_draft slack rule), the
    ``decode_per_row`` model variants, the run, and stats packaging —
    one place, so the two public entry points cannot drift."""
    import dataclasses

    B, P = prompt.shape
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if n_draft < 1:
        raise ValueError(f"n_draft must be >= 1, got {n_draft}")
    total = P + max_new_tokens
    for m, label in ((model, "model"), (draft_model, "draft_model")):
        if total + n_draft > m.config.max_seq:
            raise ValueError(
                f"prompt ({P}) + max_new_tokens ({max_new_tokens}) + "
                f"n_draft ({n_draft}) = {total + n_draft} exceeds {label}'s "
                f"max_seq ({m.config.max_seq}); the verify chunk can write "
                f"up to n_draft slots past the final token — size max_seq "
                f"with that slack"
            )
        if (getattr(m.config, "decode_rolling_cache", False)
                and n_draft + 1 > m.config.decode_rolling_slack):
            raise ValueError(
                f"n_draft + 1 = {n_draft + 1} exceeds {label}'s "
                f"decode_rolling_slack ({m.config.decode_rolling_slack}) "
                f"— the verify chunk must fit the rolling cache's slack "
                f"region"
            )
    per_row = lambda m: type(m)(  # noqa: E731
        dataclasses.replace(m.config, decode_per_row=True,
                            **_pending_kw(m, n_draft))
    )
    buf, (rounds, drafted, accepted) = _spec_batched_run(
        per_row(model), per_row(draft_model), params, draft_params, prompt,
        key, temperature, max_new_tokens=max_new_tokens, n_draft=n_draft,
        eos_token=eos_token, sampled=sampled, top_k=top_k, top_p=top_p,
    )
    if return_stats:
        return buf, {"rounds": int(rounds),
                     "drafted": np.asarray(drafted),
                     "accepted": np.asarray(accepted)}
    return buf


def speculative_generate_batched(
    model: Any,
    params: Any,
    draft_model: Any,
    draft_params: Any,
    prompt: jax.Array,
    max_new_tokens: int,
    n_draft: int = 4,
    return_stats: bool = False,
    eos_token: Optional[int] = None,
) -> Any:
    """Batched, device-resident greedy speculative decoding.

    Same exactness contract as :func:`speculative_generate` — the output
    equals ``generate(model, params, prompt, ..., temperature=0.0)`` row
    for row — but serving-shaped (VERDICT r4 next #4):

    - **any batch size**: every row keeps its own KV-cache frontier
      (``TransformerConfig.decode_per_row``), so rows accept different
      draft counts per round and still share one target forward;
    - **no per-token host sync**: the draft chain is a fused
      ``lax.scan`` and the round loop a ``lax.while_loop`` — the whole
      generation is ONE dispatch, tokens come back at the end;
    - still exactly one target verification forward per round.

    The drafting scan runs ``n_draft + 1`` single-token draft steps (the
    extra step keeps the draft cache covering the full chunk, removing
    the variable-length catch-up feed the host loop needed), and the
    fastest row waits on the slowest row's round count — at large batch
    a round only helps rows still decoding.  Requires ``prompt_len +
    max_new_tokens + n_draft <= max_seq`` on BOTH models (the verify
    chunk of a nearly-finished row writes up to ``n_draft`` slots past
    its last token).

    Returns ``[B, P + max_new_tokens]`` tokens; with
    ``return_stats=True`` also ``{"rounds": int, "drafted": [B],
    "accepted": [B]}`` (per-row numpy counts).
    """
    return _spec_batched_call(
        model, draft_model, params, draft_params, prompt,
        max_new_tokens, n_draft, eos_token, return_stats,
    )


def speculative_sample_batched(
    model: Any,
    params: Any,
    draft_model: Any,
    draft_params: Any,
    prompt: jax.Array,
    max_new_tokens: int,
    n_draft: int = 4,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    rng: Optional[jax.Array] = None,
    return_stats: bool = False,
    eos_token: Optional[int] = None,
) -> Any:
    """Batched, device-resident speculative SAMPLING — the
    ``temperature > 0`` counterpart of
    :func:`speculative_generate_batched`, sharing its round loop,
    per-row KV frontiers and max_seq slack requirement.  The draft
    proposes from its own distribution q inside the fused scan, the
    target verifies the chunk in one forward, and each proposal is
    accepted with probability ``min(1, p/q)`` with a residual resample
    on rejection (:func:`_accept_resample_rows`) — emitted tokens are
    distributed EXACTLY per the target's sampling distribution whatever
    the draft is.  All randomness is jax PRNG keyed by ``rng``, so a
    fixed key gives a reproducible trace with zero host round-trips
    (the host-loop :func:`speculative_sample` keeps numpy RNG and
    batch=1).

    Returns ``[B, P + max_new_tokens]`` tokens; with
    ``return_stats=True`` also ``{"rounds": int, "drafted": [B],
    "accepted": [B]}``.
    """
    if temperature <= 0.0:
        raise ValueError(
            "speculative_sample_batched needs temperature > 0; use "
            "speculative_generate_batched for greedy decoding"
        )
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if top_k is not None and top_k < 1:
        # validate here: an invalid k otherwise dies deep inside the
        # jitted trace with an opaque lax.top_k error
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    key = rng if rng is not None else jax.random.PRNGKey(0)
    return _spec_batched_call(
        model, draft_model, params, draft_params, prompt,
        max_new_tokens, n_draft, eos_token, return_stats,
        key=key, temperature=jnp.float32(temperature), sampled=True,
        top_k=top_k,
        top_p=None if top_p is None else jnp.float32(top_p),
    )


@functools.partial(
    jax.jit, static_argnums=(0, 1),
    static_argnames=("max_new_tokens", "eos_token", "sampled", "top_k"),
)
def _spec_prefill(model, draft_model, params, draft_params, prompt,
                  key=None, temperature=0.0, *, max_new_tokens, eos_token,
                  sampled=False, top_k=None, top_p=None):
    """Jitted step-API entry: prefill a fresh batch and return the
    device-resident round state (see :func:`_spec_prefill_impl`)."""
    return _spec_prefill_impl(
        model, draft_model, params, draft_params, prompt, key, temperature,
        max_new_tokens=max_new_tokens, eos_token=eos_token, sampled=sampled,
        top_k=top_k, top_p=top_p,
    )


@functools.partial(
    jax.jit, static_argnums=(0, 1), donate_argnums=(4,),
    static_argnames=("n_draft", "eos_token", "sampled", "top_k"),
)
def _spec_round(model, draft_model, params, draft_params, state,
                temperature=0.0, *, n_draft, eos_token, sampled=False,
                top_k=None, top_p=None):
    """Jitted step-API entry: execute ONE speculative decode round on a
    :func:`_spec_prefill` state.  Module-level jit with the (hashable)
    flax modules static: a serving loop pays one compile per (model,
    batch shape), then every round is a single cheap dispatch.

    ``state`` is donated, here and in every entry that takes a round
    state and returns its successor (:func:`_spec_admit`,
    :func:`_spec_import_row`, :func:`_mtp_round`, :func:`_mtp_admit`):
    the caches are updated where they lie, and the arrays handed in are
    deleted.  Nothing else is: not the parameters, not a prompt row, not
    the caches of a :class:`KVHandoff`, which its owner may keep."""
    return _spec_round_impl(
        model, draft_model, params, draft_params, state, temperature,
        n_draft=n_draft, eos_token=eos_token, sampled=sampled,
        top_k=top_k, top_p=top_p,
    )


@functools.partial(
    jax.jit, static_argnums=(0, 1), donate_argnums=(4,),
    static_argnames=("eos_token", "sampled", "top_k"),
)
def _spec_admit(model, draft_model, params, draft_params, state, row,
                prompt_row, key=None, temperature=0.0, *, eos_token,
                sampled=False, top_k=None, top_p=None):
    """Admit ONE new request into row ``row`` of a half-finished batch
    between rounds: prefill its prompt at batch 1, scatter the K/V rows
    into the batch caches, and reset the row's buffer / frontier / done
    flag / per-row stats.  The other rows' state is untouched — they
    continue decoding next round as if nothing happened.

    Stale K/V the previous occupant left beyond the fresh prompt need no
    clearing: with per-row frontiers their key positions exceed every
    query position the new request will ever issue below them, so the
    causal mask hides them until the new request overwrites them in
    place (the same no-rewind argument as :func:`_spec_round_impl`).
    """
    (buf, n_tok, done, cache_t, cache_d, key_st,
     (rounds, drafted, accepted)) = state[:7]
    total = buf.shape[1]
    if key is None:
        key = jax.random.PRNGKey(0)
    P_new = prompt_row.shape[1]

    c1_t, last = _row_prefill(model, params, prompt_row)
    c1_d, _ = _row_prefill(draft_model, draft_params, prompt_row)
    if sampled:
        key, kg = jax.random.split(key)
        g = jax.random.categorical(
            kg, _truncate_logits(last / temperature, top_k, top_p),
            axis=-1,
        ).astype(jnp.int32)[0]
    else:
        g = jnp.argmax(last, axis=-1).astype(jnp.int32)[0]

    row_buf = jnp.zeros((total,), jnp.int32)
    row_buf = jax.lax.dynamic_update_slice(row_buf, prompt_row[0], (0,))
    row_buf = row_buf.at[P_new].set(g)
    buf = buf.at[row].set(row_buf)
    n_tok = n_tok.at[row].set(P_new + 1)
    row_done = (g == eos_token) if eos_token is not None \
        else jnp.asarray(False)
    done = done.at[row].set(row_done)

    cache_t = _scatter_row(cache_t, c1_t, row)
    cache_d = _scatter_row(cache_d, c1_d, row)
    drafted = drafted.at[row].set(0)
    accepted = accepted.at[row].set(0)
    return (buf, n_tok, done, cache_t, cache_d, key_st,
            (rounds, drafted, accepted)) + tuple(state[7:])


@functools.partial(jax.jit, donate_argnums=(0,))
def _spec_import_row(state, row, buf1, n1, d1, c1_t, c1_d):
    """Scatter a handed-off batch-1 row state into row ``row`` of a live
    batch state — the IMPORT half of the prefill/decode lane handoff.

    The same :func:`_scatter_row` as :func:`_spec_admit`'s, minus the
    prefill: the handoff already carries the
    prefilled cache rows, so importing a row is a cheap scatter dispatch
    instead of a full prompt forward.  Stale K/V the previous occupant
    left beyond the fresh prompt are hidden by the per-row causal mask,
    the same no-rewind argument as :func:`_spec_admit`.  A state that
    keeps device counters keeps them as they are."""
    (buf, n_tok, done, cache_t, cache_d, key_st,
     (rounds, drafted, accepted)) = state[:7]
    buf = buf.at[row].set(buf1[0])
    n_tok = n_tok.at[row].set(n1[0])
    done = done.at[row].set(d1[0])

    cache_t = _scatter_row(cache_t, c1_t, row)
    cache_d = _scatter_row(cache_d, c1_d, row)
    drafted = drafted.at[row].set(0)
    accepted = accepted.at[row].set(0)
    return (buf, n_tok, done, cache_t, cache_d, key_st,
            (rounds, drafted, accepted)) + tuple(state[7:])


@functools.partial(
    jax.jit, static_argnums=(0, 1),
    static_argnames=("max_new_tokens", "eos_token", "sampled", "top_k"),
)
def _spec_suffix_prefill(model, draft_model, params, draft_params, prompt,
                         suffix, pos0, cache_t, cache_d, key=None,
                         temperature=0.0, *, max_new_tokens, eos_token,
                         sampled=False, top_k=None, top_p=None):
    """Continue a PARTIAL prefill: ``cache_t``/``cache_d`` already hold
    K/V for the first ``pos0`` prompt positions (imported prefix pages,
    zero beyond them) and ``suffix = prompt[:, pos0:]`` runs through the
    decode path at positions ``pos0..P-1`` — building the exact round
    state :func:`_spec_prefill_impl` would have built from a full
    prefill.  Bit-equality argument: K/V at a position is a function of
    the tokens at or before it only (causal attention over the WRITTEN
    cache), so a suffix forward on top of the prefix's exact pages
    reproduces the full prefill leaf for leaf — the prefix-cache oracle
    in ``tests/test_kvstore.py`` asserts this for f32 and int8 layouts.
    ``pos0`` is a traced scalar, so one compile covers every split point
    sharing the same ``(P, S)`` shape pair; the edge is ledger-exempt
    like the other shape-polymorphic admission edges."""
    B, P = prompt.shape
    S = suffix.shape[1]
    total = P + max_new_tokens
    if key is None:
        key = jax.random.PRNGKey(0)
    pos = jnp.broadcast_to(
        pos0 + jnp.arange(S, dtype=jnp.int32)[None, :], (B, S)
    )
    out, mut = model.apply(
        {"params": params, "cache": cache_t},
        {"tokens": suffix, "positions": pos},
        decode=True, mutable=["cache"],
    )
    cache_t = mut["cache"]
    last = out["logits"][:, -1].astype(jnp.float32)
    _, mut_d = draft_model.apply(
        {"params": draft_params, "cache": cache_d},
        {"tokens": suffix, "positions": pos},
        decode=True, mutable=["cache"],
    )
    cache_d = mut_d["cache"]
    if sampled:
        key, kg = jax.random.split(key)
        g = jax.random.categorical(
            kg, _truncate_logits(last / temperature, top_k, top_p),
            axis=-1,
        ).astype(jnp.int32)
    else:
        g = jnp.argmax(last, axis=-1).astype(jnp.int32)
    buf = jnp.zeros((B, total), jnp.int32)
    buf = jax.lax.dynamic_update_slice(buf, prompt, (0, 0))
    buf = buf.at[:, P].set(g)
    n_tok = jnp.full((B,), P + 1, jnp.int32)
    done = (g == eos_token) if eos_token is not None \
        else jnp.zeros((B,), bool)
    stats0 = (jnp.zeros((), jnp.int32),
              jnp.zeros((B,), jnp.int32),
              jnp.zeros((B,), jnp.int32))
    return buf, n_tok, done, cache_t, cache_d, key, stats0


# -- a draft that reads the target's hidden state (MTPDraft) -----------------
#
# The round state of the functions above, with two more entries appended:
# ``(buf, n_tok, done, cache_t, cache_d, key, (rounds, drafted, accepted),
# draft_tok [B], counters)``.  ``draft_tok`` is each row's pending proposal
# for the token after its frontier; ``counters`` are totals accumulated on
# the device and fetched when someone asks (``ContinuousBatcher.stats``).


def _draft_apply(draft_model, draft_params, params, cache_d, tokens,
                 positions, hidden, idle=None, **kw):
    """One pass of the hidden-state draft over ``(hidden_i, tokens_i =
    t_{i+1})`` pairs, with the target's embedding and head handed in
    (and, in a round, which rows are finished: ``idle``)."""
    batch = {"tokens": tokens, "positions": positions, "hidden": hidden,
             **draft_model.tied(params)}
    if idle is not None:
        batch["idle"] = idle
    return draft_model.apply(
        {"params": draft_params, "cache": cache_d}, batch,
        decode=True, mutable=["cache", "routing"], **kw,
    )


def _routed_layers(model) -> int:
    """Layers of ``model`` that sow their routing, by its config's own
    account (``experts`` from ``first_k_dense`` on)."""
    cfg = model.config
    if getattr(cfg, "experts", None) is None:
        return 0
    return cfg.n_layers - getattr(cfg, "first_k_dense", 0)


def _selects(model) -> bool:
    """Whether ``model``'s attention chooses its keys (``config.select``)."""
    return getattr(model.config, "select", None) is not None


def _round_counts(model, draft_model) -> bool:
    """Whether a two-model round keeps device counters: where a model has
    routed experts or a selection to count.  Every other pair's round
    state, and so its compiled programs, hold none."""
    return any(_routed_layers(m) or _selects(m)
               for m in (model, draft_model))


def _zero_counters(model, draft_model):
    """Totals of the rounds since the last fetch: tokens each held expert
    got (a row a routed layer, the target's then the draft's), top-k slots
    routed in all and those that fell on a held expert, drafts proposed and
    accepted, rounds; and, where a model chooses its keys, the keys its
    live rows' queries kept and the keys they could see, summed over
    queries and selecting layers."""
    experts = [ex for ex in (getattr(m.config, "experts", None)
                             for m in (model, draft_model)) if ex is not None]
    held = max((ex.held for ex in experts), default=0)
    layers = _routed_layers(model) + _routed_layers(draft_model)
    zero = jnp.zeros((), jnp.int32)
    counters = {"rounds": zero, "drafted": zero, "accepted": zero,
                "routed_slots": zero, "held_slots": zero,
                "expert_tokens": jnp.zeros((layers, held), jnp.int32)}
    if _selects(model) or _selects(draft_model):
        # a round of 16 rows near 20,000 keys sees millions of keys, and
        # 32 bits are full after a few hundred rounds: (high, low) pairs,
        # :func:`_wide_add`, made one number when they are fetched
        counters.update({name: jnp.zeros((2,), jnp.int32)
                         for name in _WIDE_COUNTERS})
    return counters


_WIDE_COUNTERS = ("selected_keys", "live_keys")
_WIDE_BITS = 20


def _wide_add(pair, x):
    """``pair`` ``[2]`` int32 ``(high, low)`` standing for ``high * 2**20 +
    low``, plus ``x`` (int32, under ``2**31 - 2**20``): 51 bits of count
    where the device has no 64-bit integers."""
    low = pair[1] + x
    return jnp.stack([pair[0] + (low >> _WIDE_BITS),
                      low & ((1 << _WIDE_BITS) - 1)])


def _narrow(counters):
    """Fetched counters with every ``(high, low)`` pair as one number."""
    return {k: (np.int64(v[0]) << _WIDE_BITS) + np.int64(v[1])
            if k in _WIDE_COUNTERS else v
            for k, v in counters.items()}


def _count_pass(model, mutated, live, counters):
    """What one pass of ``model`` sowed, over the ``live`` rows ``[B]``:
    ``(tokens a held expert [layers, held], slots routed, (keys kept, keys
    seen))``."""
    tokens, slots = _count_routing(
        model, mutated.get("routing"), live,
        counters["expert_tokens"].shape[1])
    keys = jnp.zeros((2,), jnp.int32)
    for leaf in jax.tree_util.tree_leaves(mutated.get("selection")):
        keys = keys + jnp.sum(                       # leaf: [B, S, 2]
            jnp.where(live[:, None, None], leaf, 0), axis=(0, 1))
    return tokens, slots, keys


def _add_counts(counters, target, draft, n_drafted, n_accepted):
    """``counters`` after one more round: ``target`` and ``draft`` are the
    round's :func:`_count_pass` of each model."""
    tokens = jnp.concatenate([target[0], draft[0]], axis=0)
    out = {
        "rounds": counters["rounds"] + 1,
        "drafted": counters["drafted"] + n_drafted,
        "accepted": counters["accepted"] + n_accepted,
        "routed_slots": counters["routed_slots"] + target[1] + draft[1],
        "held_slots": counters["held_slots"] + jnp.sum(tokens),
        "expert_tokens": counters["expert_tokens"] + tokens,
    }
    if "selected_keys" in counters:
        keys = target[2] + draft[2]
        out["selected_keys"] = _wide_add(counters["selected_keys"], keys[0])
        out["live_keys"] = _wide_add(counters["live_keys"], keys[1])
    return out


def _count_routing(model, routing, live, held):
    """``([layers, held] tokens a held expert, slots routed)`` of one pass:
    ``routing`` is the pass's sown ``top_idx`` leaves, ``live`` the rows
    ``[B]`` whose tokens count."""
    ex = getattr(model.config, "experts", None)
    if ex is None or not routing:
        return jnp.zeros((0, held), jnp.int32), jnp.zeros((), jnp.int32)
    rows, slots = [], jnp.zeros((), jnp.int32)
    for name in sorted(routing, key=lambda k: int(k.rsplit("_", 1)[1])):
        idx = routing[name]["experts"]["top_idx"][0]          # [B, S, K]
        local = idx - ex.held_start
        here = (local >= 0) & (local < ex.held) & live[:, None, None]
        rows.append(jnp.zeros((ex.held,), jnp.int32).at[
            jnp.where(here, local, ex.held)].add(1, mode="drop"))
        slots = slots + jnp.sum(live) * idx.shape[1] * idx.shape[2]
    return jnp.stack(rows), slots.astype(jnp.int32)


def _mtp_prefill_rows(model, draft_model, params, draft_params, prompt):
    """Prefill target and draft over ``prompt`` ``[B, P]`` from empty
    caches: the target's pass gives the hidden states and the first
    emitted token ``g``; the draft's pass over ``(h_i, t_{i+1})`` (``t_P =
    g``) fills its cache and proposes the token after ``g``."""
    B, P = prompt.shape
    pos = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32), (B, P))
    out, mut = model.apply(
        {"params": params, "cache": zero_cache(model, params, prompt)},
        {"tokens": prompt, "positions": pos},
        decode=True, mutable=["cache"], **_prefill_kw(model),
    )
    g = jnp.argmax(out["logits"][:, -1].astype(jnp.float32),
                   axis=-1).astype(jnp.int32)
    nxt = jnp.concatenate([prompt[:, 1:], g[:, None]], axis=1)
    # the draft's empty cache takes its type from what the draft is given:
    # from the tokens alone it would be float32, after the zeros that stand
    # in for the hidden states
    d_out, d_mut = _draft_apply(
        draft_model, draft_params, params,
        zero_cache(draft_model, draft_params, prompt,
                   {"hidden": out["hidden"], **draft_model.tied(params)}),
        nxt, pos, out["hidden"], **_prefill_kw(draft_model),
    )
    d_tok = jnp.argmax(d_out["logits"][:, -1].astype(jnp.float32),
                       axis=-1).astype(jnp.int32)
    return mut["cache"], d_mut["cache"], g, d_tok


@functools.partial(
    jax.jit, static_argnums=(0, 1),
    static_argnames=("max_new_tokens", "eos_token"),
)
def _mtp_prefill(model, draft_model, params, draft_params, prompt, key=None,
                 *, max_new_tokens, eos_token):
    """:func:`_spec_prefill` for a hidden-state draft (greedy)."""
    B, P = prompt.shape
    total = P + max_new_tokens
    if key is None:
        key = jax.random.PRNGKey(0)
    cache_t, cache_d, g, d_tok = _mtp_prefill_rows(
        model, draft_model, params, draft_params, prompt)
    buf = jnp.zeros((B, total), jnp.int32)
    buf = jax.lax.dynamic_update_slice(buf, prompt, (0, 0))
    buf = buf.at[:, P].set(g)
    n_tok = jnp.full((B,), P + 1, jnp.int32)
    done = (g == eos_token) if eos_token is not None \
        else jnp.zeros((B,), bool)
    stats0 = (jnp.zeros((), jnp.int32), jnp.zeros((B,), jnp.int32),
              jnp.zeros((B,), jnp.int32))
    return (buf, n_tok, done, cache_t, cache_d, key, stats0, d_tok,
            _zero_counters(model, draft_model))


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(4,),
                   static_argnames=("eos_token",))
def _mtp_round(model, draft_model, params, draft_params, state, *,
               eos_token):
    """ONE round with a hidden-state draft of depth one (greedy).

    The target verifies ``[pending, d_1]`` per row at the row's frontier
    (the same per-row positions and no-rewind argument as
    :func:`_spec_round_impl`), accepts ``d_1`` iff it is the target's own
    arg-max, and emits one or two tokens ``y`` — always the target's
    arg-maxes, so the output is plain greedy decoding whatever the draft
    proposed.  Then ONE pass of the draft over the chunk's ``(h_i, y_i)``
    pairs fills its cache (the second slot is stale when ``d_1`` was
    refused, and overwritten next round) and its proposal at the row's
    last accepted position is the next ``d_1``."""
    (buf, n_tok, done_in, cache_t, cache_d, key, (rounds, drafted, accepted),
     d_tok, counters) = state
    B, total = buf.shape
    ar = jnp.arange(2, dtype=jnp.int32)[None, :]
    pos = n_tok - 1                                     # [B] frontiers
    pending = jnp.take_along_axis(buf, pos[:, None], axis=1)[:, 0]
    # Both models are told which rows are finished (``"idle"``): what such
    # a row emits is dropped below (``keep``), so its attention may read
    # nothing (``LatentAttention``, ``Attention._decode_attend``) and a
    # round's attention follows the rows in use.  Its chunk is written at
    # its frontier as ever: no slot below it changes while the row waits to
    # be harvested.
    positions = pos[:, None] + ar
    out, mut = model.apply(
        {"params": params, "cache": cache_t},
        {"tokens": jnp.stack([pending, d_tok], axis=1),
         "positions": positions, "idle": done_in},
        decode=True, mutable=["cache", "routing"],
    )
    y = jnp.argmax(out["logits"].astype(jnp.float32), axis=-1) \
        .astype(jnp.int32)                              # [B, 2]
    j = (d_tok == y[:, 0]).astype(jnp.int32)            # [B], 0 or 1

    keep = ar <= j[:, None]
    if eos_token is not None:
        keep = keep & jnp.stack(
            [jnp.ones((B,), bool), y[:, 0] != eos_token], axis=1)
    keep = keep & ((n_tok[:, None] + ar) < total) & ~done_in[:, None]
    cols = jnp.where(keep, n_tok[:, None] + ar, total)  # OOB -> dropped
    rows = jnp.broadcast_to(jnp.arange(B)[:, None], cols.shape)
    buf = buf.at[rows, cols].set(y, mode="drop")
    acc = keep.sum(axis=1).astype(jnp.int32)
    n_new = n_tok + acc
    done = done_in | (n_new >= total)
    if eos_token is not None:
        done = done | jnp.any((y == eos_token) & keep, axis=1)

    d_out, d_mut = _draft_apply(draft_model, draft_params, params, cache_d,
                                y, positions, out["hidden"], idle=done_in)
    proposals = jnp.argmax(d_out["logits"].astype(jnp.float32), axis=-1) \
        .astype(jnp.int32)
    d_next = jnp.take_along_axis(proposals, j[:, None], axis=1)[:, 0]

    live = ~done_in
    n_drafted = jnp.where(live, jnp.minimum(1, total - n_tok), 0)
    n_accepted = jnp.where(live, jnp.minimum(j, acc), 0)
    counters = _add_counts(
        counters, _count_pass(model, mut, live, counters),
        _count_pass(draft_model, d_mut, live, counters),
        jnp.sum(n_drafted), jnp.sum(n_accepted))
    stats = (rounds + 1, drafted + n_drafted, accepted + n_accepted)
    return (buf, n_new, done, mut["cache"], d_mut["cache"], key, stats,
            d_next, counters)


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(4,),
                   static_argnames=("eos_token",))
def _mtp_admit(model, draft_model, params, draft_params, state, row,
               prompt_row, *, eos_token):
    """:func:`_spec_admit` for a hidden-state draft: the new row's target
    prefill, the draft's prefill from its hidden states, both scattered
    into the batch caches, and the row's first proposal."""
    (buf, n_tok, done, cache_t, cache_d, key, (rounds, drafted, accepted),
     d_tok, counters) = state
    total = buf.shape[1]
    P_new = prompt_row.shape[1]
    c1_t, c1_d, g, d1 = _mtp_prefill_rows(
        model, draft_model, params, draft_params, prompt_row)
    row_buf = jnp.zeros((total,), jnp.int32)
    row_buf = jax.lax.dynamic_update_slice(row_buf, prompt_row[0], (0,))
    row_buf = row_buf.at[P_new].set(g[0])
    buf = buf.at[row].set(row_buf)
    n_tok = n_tok.at[row].set(P_new + 1)
    done = done.at[row].set(
        (g[0] == eos_token) if eos_token is not None else False)

    return (buf, n_tok, done, _scatter_row(cache_t, c1_t, row),
            _scatter_row(cache_d, c1_d, row), key, (rounds, drafted.at[row].set(0), accepted.at[row].set(0)),
            d_tok.at[row].set(d1[0]), counters)


@dataclasses.dataclass
class KVPage:
    """One fixed-granularity slice of a prefilled row: ``page_tokens``
    consecutive token ids plus both models' K/V cache slots for exactly
    those positions.  Payload leaves (int8 payload and its rank-4 scales
    alike) are sliced along their slot axis (:func:`_slot_axis`); scalar
    leaves
    (``cache_index``) ride along so :meth:`KVHandoff.from_pages` can
    rebuild a tree with the original structure.  Leaves are OWNED copies
    (never views), so a page's ``nbytes`` is its true retained size —
    the unit the :class:`~rocket_tpu.serve.kvstore.PrefixKVStore` byte
    budget accounts in."""

    tokens: Any
    cache_t: Any
    cache_d: Any

    @property
    def page_tokens(self) -> int:
        return int(self.tokens.shape[0])

    @property
    def nbytes(self) -> int:
        leaves = jax.tree_util.tree_leaves(
            (self.tokens, self.cache_t, self.cache_d))
        return int(sum(leaf.nbytes for leaf in leaves))

    def layout_sig(self):
        """Shape/dtype signature of the cache leaves (token count
        excluded from shapes only via the slot axis, which IS part of
        the signature — pages of different granularity never mix)."""
        return tuple(
            (tuple(leaf.shape), str(leaf.dtype))
            for leaf in jax.tree_util.tree_leaves(
                (self.cache_t, self.cache_d))
        )


@dataclasses.dataclass
class KVHandoff:
    """One request's finished prefill, packaged for a cross-replica
    handoff: the batch-1 buffer row (prompt + first emitted token), its
    frontier and done flag, and both models' prefilled KV-cache rows.

    The transfer is BOUNDED by construction: rolling-cache models keep
    ``attention_window + decode_rolling_slack`` slots per row however
    long the prompt, and with ``kv_cache_int8`` the pages travel as int8
    payload WITH their rank-4 ``[1, slots, KV, 1]`` f32 scale leaves —
    both are payload leaves, so export, transfer, and the import scatter
    treat them uniformly.  :meth:`to_host` materializes every leaf as
    numpy, the wire format a process-backed replica would ship.
    """

    buf: Any
    n_tok: Any
    done: Any
    cache_t: Any
    cache_d: Any

    def _tree(self):
        return (self.buf, self.n_tok, self.done, self.cache_t,
                self.cache_d)

    def to_host(self) -> "KVHandoff":
        """Copy every leaf to host numpy (blocks on the prefill)."""
        return KVHandoff(*jax.tree_util.tree_map(np.asarray, self._tree()))

    @property
    def total_len(self) -> int:
        return int(self.buf.shape[1])

    @property
    def nbytes(self) -> int:
        """Transfer size of the packaged row — ``fleet/handoff_bytes``
        telemetry; int8 caches are ~4x smaller than f32 here."""
        return int(sum(leaf.nbytes
                       for leaf in jax.tree_util.tree_leaves(self._tree())))

    def split_pages(self, page_tokens: int) -> "list[KVPage]":
        """Split this row's REUSABLE prefix into fixed-size
        :class:`KVPage`\\ s (host copies, oldest first).

        The reusable prefix is the first ``n_tok - 1`` positions: each
        holds K/V computed from the accepted token at that position,
        while the FINAL token's slot can still be a stale speculative
        write (the round loop re-feeds it instead of reading it back,
        so decode never notices — but a prefix consumer would).  Only
        full pages split out; the remainder is the consumer's suffix to
        re-prefill."""
        if page_tokens < 1:
            raise ValueError(f"page_tokens must be >= 1, got {page_tokens}")
        usable = int(np.asarray(self.n_tok)[0]) - 1
        n_pages = max(0, usable) // page_tokens
        if n_pages == 0:
            return []
        buf = np.asarray(self.buf)
        cache_t, cache_d = jax.tree_util.tree_map(
            np.asarray, (self.cache_t, self.cache_d))

        def page_slice(path, a, lo, hi):
            # owned copies: a view would retain the whole parent buffer
            # and break the store's byte accounting
            if _is_cache_payload(path, a):
                cut = [slice(None)] * a.ndim
                cut[_slot_axis(path)] = slice(lo, hi)
                return np.ascontiguousarray(a[tuple(cut)])
            return np.asarray(a).copy()

        pages = []
        for i in range(n_pages):
            lo, hi = i * page_tokens, (i + 1) * page_tokens
            pages.append(KVPage(
                tokens=buf[0, lo:hi].copy(),
                cache_t=jax.tree_util.tree_map_with_path(
                    lambda p, a: page_slice(p, a, lo, hi), cache_t),
                cache_d=jax.tree_util.tree_map_with_path(
                    lambda p, a: page_slice(p, a, lo, hi), cache_d),
            ))
        return pages

    @classmethod
    def from_pages(cls, pages, *, total_len: int, slots_t: int,
                   slots_d: int) -> "KVHandoff":
        """Reassemble contiguous pages (oldest first) into a
        PREFIX-shaped handoff: ``buf`` holds the covered tokens,
        ``n_tok`` the covered count, ``done=False``, and every cache
        leaf is zero past the covered slots — exactly what a fresh
        prefill's untouched tail holds, so a suffix prefill continued
        on top (:func:`_spec_suffix_prefill`) is bit-equal to a full
        one.  ``slots_t``/``slots_d`` give each model's total cache
        slot count (``max_seq`` for the position==slot layout the page
        index assumes); the scalar ``cache_index`` leaves are set to
        the covered frontier."""
        if not pages:
            raise ValueError("from_pages needs at least one page")
        covered = sum(p.page_tokens for p in pages)
        if covered + 1 > total_len:
            raise ValueError(
                f"pages cover {covered} tokens; total_len ({total_len}) "
                f"needs room for at least one generated token"
            )

        def join(trees, slots):
            if covered > slots:
                raise ValueError(
                    f"pages cover {covered} tokens but the cache has "
                    f"only {slots} slots"
                )

            def leaf_join(path, *leaves):
                a0 = np.asarray(leaves[0])
                if not _is_cache_payload(path, a0):
                    return np.asarray(covered, a0.dtype)  # cache_index
                ax = _slot_axis(path)
                cat = np.concatenate(
                    [np.asarray(leaf) for leaf in leaves], axis=ax)
                room = [(0, 0)] * cat.ndim
                room[ax] = (0, slots - cat.shape[ax])
                return np.pad(cat, room)

            return jax.tree_util.tree_map_with_path(leaf_join, *trees)

        buf = np.zeros((1, total_len), np.int32)
        buf[0, :covered] = np.concatenate(
            [np.asarray(p.tokens, np.int32) for p in pages])
        return cls(
            buf=buf,
            n_tok=np.array([covered], np.int32),
            done=np.array([False]),
            cache_t=join([p.cache_t for p in pages], slots_t),
            cache_d=join([p.cache_d for p in pages], slots_d),
        )


def export_kv_row(state, row: int) -> KVHandoff:
    """Slice one row of a batched round state into a :class:`KVHandoff`.

    Payload leaves (K/V, int8 scales and the indexer's keys alike) slice
    to batch 1; scalar leaves (``cache_index``) copy whole — the exact
    inverse discrimination :func:`_spec_import_row` applies on import.
    A recurrent state is refused: a handoff carries slots, not states.
    Every leaf of the handoff is a buffer of its own: the next round or
    admission donates ``state``, and a handoff may outlive it (the prefix
    store, a parked preemption, a peer).
    Used by :meth:`ContinuousBatcher.prefill_handoff` (row 0 of a fresh
    batch-1 prefill) and available for migrating a live row between
    replicas."""
    if len(state) > 8:
        raise ValueError(
            "KVHandoff cannot carry the state of a hidden-state draft's "
            "round (its pending draft token and counters) yet")
    # a two-model round's device counters (an eighth entry) stay behind:
    # they are the batch's, not the row's
    (buf, n_tok, done, cache_t, cache_d, _key, _stats) = state[:7]
    if any(_is_row_state(path) for path, _ in
           jax.tree_util.tree_flatten_with_path((cache_t, cache_d))[0]):
        raise ValueError("KVHandoff cannot carry a state-space layer's "
                         "recurrent state yet")
    sl = lambda p, a: (a[row:row + 1] if _is_cache_payload(p, a)  # noqa: E731
                       else jnp.array(a, copy=True))
    return KVHandoff(
        buf=buf[row:row + 1],
        n_tok=n_tok[row:row + 1],
        done=done[row:row + 1],
        cache_t=jax.tree_util.tree_map_with_path(sl, cache_t),
        cache_d=jax.tree_util.tree_map_with_path(sl, cache_d),
    )


class HostReads:
    """Every blocking device→host read on the serving round path goes
    through one of these: it opens a ``serve/fetch`` span (``what=``,
    ``bytes=``) around the read, bumps ``counters.host_fetches`` and
    stamps when the read returned, so the loop can tell how long the host
    took from its last read to the next ``serve/dispatch``.  A bare
    batcher has one of its own on the process tracer; a ``ServingLoop``
    hands its batchers one with its tracer and its ``ServeCounters``."""

    __slots__ = ("tracer", "counters", "returned_at")

    def __init__(self, tracer: Any = None, counters: Any = None) -> None:
        self.tracer = tracer if tracer is not None else get_tracer()
        self.counters = counters
        self.returned_at: Optional[float] = None  # perf_counter seconds

    def __call__(self, x: Any, what: str, read: Any = np.asarray) -> Any:
        with self.tracer.span("serve/fetch", what=what,
                              bytes=int(getattr(x, "nbytes", 0))):
            out = read(x)
        if self.counters is not None:
            self.counters.host_fetches += 1
        self.returned_at = time.perf_counter()
        return out


class ContinuousBatcher:
    """Round-granular continuous batching over the batched speculative
    decoder — the serving-loop counterpart of the one-dispatch
    :func:`speculative_generate_batched`.

    The one-dispatch path pads whole request groups: a new arrival waits
    for the current group's SLOWEST row before any of its tokens exist.
    This driver runs the identical round body one call at a time
    (:func:`_spec_round` — same :func:`_spec_round_impl` the while_loop
    uses, behind a persistent module-level jit), keeping the carry state
    on device between calls, so the host can admit a fresh request into
    a finished row between rounds (:meth:`admit`) while the other rows
    keep decoding.  Driving :meth:`step` until every row finishes
    reproduces the one-dispatch output bit for bit (tested): both paths
    run the same prefill and round computations in the same order with
    the same key threading.

    ``state`` is DONATED to every round, admission and import: the call
    writes the caches in place and the arrays of the state it was given
    are deleted once it is dispatched.  The batcher rebinds ``self.state``
    to each result; a caller that wants a leaf past the next call takes
    a copy (:func:`export_kv_row` does, a host read does).

    Typical serving loop::

        b = ContinuousBatcher(model, draft, params, dparams, total_len=T)
        b.start(prompts)                    # [B, P] first group
        while requests_pending_or_decoding:
            b.step()                        # ONE speculative round
            for row in b.finished_rows():
                tokens, n = b.row_tokens(row)
                b.admit(row, next_prompt)   # joins the live batch

    ``total_len`` is the fixed per-row buffer length (prompt + output);
    every admitted prompt needs ``len(prompt) + 1 <= total_len`` and the
    models need ``total_len + n_draft <= max_seq`` (verify-chunk slack,
    same rule as the one-dispatch path).
    """

    def __init__(self, model, draft_model, params, draft_params, *,
                 total_len, n_draft=4, eos_token=None, sampled=False,
                 temperature=0.0, top_k=None, top_p=None, rng=None,
                 kv_cache_int8=None):
        import dataclasses

        if n_draft < 1:
            raise ValueError(f"n_draft must be >= 1, got {n_draft}")
        # A draft that declares ``reads_hidden`` (MTPDraft) is no language
        # model to step k+1 times: its round is the target's verify pass,
        # then one pass of the draft over the target's hidden states.
        self._hidden_draft = bool(getattr(draft_model, "reads_hidden", False))
        self.n_draft, self.sampled = int(n_draft), bool(sampled)
        self._base_models = (model, draft_model)  # for set_kv_cache_int8
        # a state-space layer's cache holds a round's unconfirmed drafts
        # pending: room for the first n_draft (the loop only lowers it)
        from rocket_tpu.ops.ssm import MAX_CHUNK
        self._pending = min(self.n_draft, MAX_CHUNK - 1)
        self._check_refusals()
        if sampled and temperature <= 0.0:
            raise ValueError(
                "sampled=True needs temperature > 0; use sampled=False "
                "for greedy decoding"
            )
        for m, label in ((model, "model"), (draft_model, "draft_model")):
            if total_len + n_draft > m.config.max_seq:
                raise ValueError(
                    f"total_len ({total_len}) + n_draft ({n_draft}) = "
                    f"{total_len + n_draft} exceeds {label}'s max_seq "
                    f"({m.config.max_seq}); the verify chunk can write up "
                    f"to n_draft slots past the final token"
                )
            if (getattr(m.config, "decode_rolling_cache", False)
                    and n_draft + 1 > m.config.decode_rolling_slack):
                raise ValueError(
                    f"n_draft + 1 = {n_draft + 1} exceeds {label}'s "
                    f"decode_rolling_slack "
                    f"({m.config.decode_rolling_slack})"
                )
        # ``kv_cache_int8=None`` inherits each model config's setting;
        # True/False overrides both models — the serve-layer knob
        # (ServingLoop forwards it) without touching user configs.
        overrides = {"decode_per_row": True}
        if kv_cache_int8 is not None:
            overrides["kv_cache_int8"] = bool(kv_cache_int8)
        per_row = lambda m: m.clone(  # noqa: E731
            config=dataclasses.replace(m.config, **overrides,
                                       **_pending_kw(m, self._pending))
        )
        self._model = per_row(model)
        self._draft_model = per_row(draft_model)
        self._params = params
        self._draft_params = draft_params
        self.total_len = int(total_len)
        self.eos_token = eos_token
        self._temperature = (
            jnp.float32(temperature) if sampled else temperature
        )
        self._top_k = top_k
        self._top_p = None if top_p is None else jnp.float32(top_p)
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        self._admits = 0
        self.state = None
        self.reads = HostReads()
        self._slab = None           # set by start(): _kernel_slab()

    def _kernel_slab(self):
        """``(slots, key block)`` of a target row's slab where the round's
        attention is a decode kernel — the rule and the block are the
        kernel's own (``ops.decode_attention``'s for K and V caches,
        ``ops.latent_attention``'s for a latent one), asked with the verify
        chunk's shapes and the cache as ``start()`` made it — else
        ``None``.  What ``ServeCounters.observe_blocks`` counts in."""
        from rocket_tpu.ops import decode_attention as da
        from rocket_tpu.ops import latent_attention as la

        cfg = self._model.config
        leaves = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self.state[3])[0]:
            leaves.setdefault(_leaf_name(path), leaf)
        if _latent(self._model):
            cache = leaves["cached_latent"]
            q = jax.ShapeDtypeStruct(
                (cache.shape[0], self.n_draft + 1, cfg.n_heads,
                 cache.shape[2]), cache.dtype)
            C = cfg.mla.kv_lora_rank
            if la.why_not(q, cache, C) is not None:
                return None
            return cache.shape[1], la.block_k_for(q, cache, C)
        # a configuration that lacks the field is not ``TransformerConfig``:
        # its attention is not ``Attention._decode_attend``'s
        if getattr(cfg, "decode_rolling_cache", True) \
                or _selects(self._model) or "cached_k" not in leaves:
            return None
        k = leaves["cached_k"]
        q = jax.ShapeDtypeStruct(
            (k.shape[0], self.n_draft + 1, cfg.n_heads, cfg.head_dim),
            k.dtype)
        if da.why_not(q, k, impl=cfg.attention) is not None:
            return None
        return k.shape[1], da.block_k_for(q, k)

    def set_kv_cache_int8(self, enabled: bool) -> None:
        """Flip the int8 KV-cache knob on both decode models.

        Only valid BEFORE :meth:`start` (or after the batch drained and
        before the next ``start``): a live device cache has a fixed
        dtype/leaf layout, and re-laying it mid-flight would discard
        every row's KV state.
        """
        import dataclasses

        if self.state is not None:
            raise ValueError(
                "set_kv_cache_int8 after start(): the live cache layout "
                "is fixed — drain the batch (or build a new batcher) "
                "before changing it"
            )
        model, draft_model = self._base_models
        rebuilt = lambda m: m.clone(  # noqa: E731
            config=dataclasses.replace(
                m.config, decode_per_row=True,
                kv_cache_int8=bool(enabled), **_pending_kw(m, self._pending),
            )
        )
        self._model = rebuilt(model)
        self._draft_model = rebuilt(draft_model)

    def _kw(self):
        return dict(eos_token=self.eos_token, sampled=self.sampled,
                    top_k=self._top_k, top_p=self._top_p)

    def _check_refusals(self) -> None:
        """What a draft that reads the target's hidden state, and a model
        with state-space layers, cannot do yet, refused by name
        (``n_draft`` is the serving loop's to set between rounds, so a
        round checks again)."""
        if any(_keeps_state(m) for m in self._base_models):
            refused = [what for what, on in (
                ("a draft that reads the target's hidden state (_mtp_*)",
                 self._hidden_draft),
                (f"n_draft={self.n_draft} (more than the {self._pending} "
                 f"tokens its caches hold pending)",
                 self.n_draft > self._pending),
            ) if on]
            if refused:
                raise ValueError(
                    f"state-space layers (mamba) cannot run with "
                    f"{', '.join(refused)} yet")
        if not self._hidden_draft:
            return
        refused = [what for what, on in (
            (f"n_draft={self.n_draft} (a draft chain deeper than one)",
             self.n_draft != 1),
            ("sampled=True", self.sampled),
        ) if on]
        if refused:
            raise ValueError(
                f"a draft that reads the target's hidden state cannot run "
                f"with {', '.join(refused)} yet")

    def _movable(self) -> bool:
        """Whether a row's state is what :class:`KVHandoff` and
        :class:`KVPage` carry: K/V caches of two language models."""
        return not (self._hidden_draft
                    or any(_latent(m) or _selects(m) or _keeps_state(m)
                           for m in (self._model, self._draft_model)))

    def _refuse_handoff(self, what: str) -> None:
        if not self._movable():
            raise ValueError(
                f"{what}: KVHandoff and KVPage cannot carry a latent cache, "
                f"the cache of an attention that chooses its keys (select: "
                f"its prefix pages and the prefix store are untested), the "
                f"recurrent state of state-space layers (mamba) or the "
                f"state of a draft that reads the target's hidden state yet")

    def start(self, prompts) -> None:
        """Prefill the first group (``[B, P]`` int32) and build the
        device-resident round state."""
        prompts = jnp.asarray(prompts)
        if prompts.ndim != 2 or prompts.shape[0] < 1 or prompts.shape[1] < 1:
            raise ValueError(
                f"start() needs a non-empty [B, P] prompt batch, got "
                f"shape {tuple(prompts.shape)}"
            )
        if not jnp.issubdtype(prompts.dtype, jnp.integer):
            raise ValueError(
                f"start() needs integer token ids, got dtype "
                f"{prompts.dtype}"
            )
        prompts = prompts.astype(jnp.int32)
        B, P = prompts.shape
        if P + 1 > self.total_len:
            raise ValueError(
                f"prompt length {P} + 1 exceeds total_len "
                f"({self.total_len}); the buffer needs room for at least "
                f"one generated token"
            )
        if self._hidden_draft:
            self.state = ledger_call(
                _mtp_prefill, "generate/spec_prefill",
                self._model, self._draft_model, self._params,
                self._draft_params, prompts, self._rng,
                max_new_tokens=self.total_len - P, eos_token=self.eos_token,
            )
        else:
            self.state = ledger_call(
                _spec_prefill, "generate/spec_prefill",
                self._model, self._draft_model, self._params,
                self._draft_params, prompts, self._rng, self._temperature,
                max_new_tokens=self.total_len - P, **self._kw(),
            )
        self._slab = self._kernel_slab()

    def step(self):
        """Run ONE speculative round on every live row; returns
        ``(n_tok [B], done [B])`` as host numpy arrays."""
        if self.state is None:
            raise ValueError("call start() before step()")
        with self.reads.tracer.span("serve/dispatch", n_draft=self.n_draft):
            self._check_refusals()
            if self._hidden_draft:
                self.state = ledger_call(
                    _mtp_round, "generate/spec_round",
                    self._model, self._draft_model, self._params,
                    self._draft_params, self.state,
                    eos_token=self.eos_token,
                )
            else:
                self.state = ledger_call(
                    _spec_round, "generate/spec_round",
                    self._model, self._draft_model, self._params,
                    self._draft_params, self.state, self._temperature,
                    n_draft=self.n_draft, **self._kw(),
                )
        n_tok = self.reads(self.state[1], "n_tok")
        done = self.reads(self.state[2], "done")
        if self._slab is not None and self.reads.counters is not None:
            self.reads.counters.observe_blocks(n_tok, done, *self._slab)
        if self.reads.counters is not None and _selects(self._model):
            self.reads.counters.observe_selection(
                n_tok, done, self._model.config.select.top_k)
        return n_tok, done

    def admit(self, row: int, prompt_row, *, preempt: bool = False) -> None:
        """Replace row ``row`` with a fresh request (``[1, P]`` or
        ``[P]`` int32) — between rounds, while other rows keep decoding.
        The target row must be finished (its request was harvested);
        overwriting a LIVE row silently drops its occupant's remaining
        tokens, so that now requires an explicit ``preempt=True``."""
        if self.state is None:
            raise ValueError("call start() before admit()")
        B = self.state[0].shape[0]
        if not 0 <= row < B:
            # the scatter's .at[row] would drop out-of-bounds writes
            # SILENTLY inside jit — fail loudly on the host instead
            raise ValueError(
                f"admit() row {row} out of range for batch of {B} rows"
            )
        if not preempt and not bool(self.reads(self.state[2], "done")[row]):
            raise ValueError(
                f"admit() into row {row} which is still decoding — "
                f"harvest it first (done flag unset), or pass "
                f"preempt=True to drop its occupant deliberately"
            )
        prompt_row = jnp.asarray(prompt_row, jnp.int32)
        if prompt_row.ndim == 1:
            prompt_row = prompt_row[None, :]
        if prompt_row.ndim != 2 or prompt_row.shape[0] != 1 \
                or prompt_row.shape[1] < 1:
            raise ValueError(
                f"admit() needs a single non-empty prompt row ([P] or "
                f"[1, P]), got shape {tuple(jnp.asarray(prompt_row).shape)}"
            )
        if prompt_row.shape[1] + 1 > self.total_len:
            raise ValueError(
                f"prompt length {prompt_row.shape[1]} + 1 exceeds "
                f"total_len ({self.total_len})"
            )
        self._admits += 1
        if self._hidden_draft:
            self.state = ledger_call(
                _mtp_admit, "generate/spec_admit",
                self._model, self._draft_model, self._params,
                self._draft_params, self.state, jnp.int32(row), prompt_row,
                _shape=int(prompt_row.shape[1]), eos_token=self.eos_token,
            )
            return
        key = jax.random.fold_in(self._rng, self._admits)
        P = int(prompt_row.shape[1])
        chunk = _admission_chunk(self._model)
        with self.reads.tracer.span(
                "generate/spec_admit", prompt_len=P,
                chunks=1 if chunk is None else -(-P // chunk)):
            self.state = ledger_call(
                _spec_admit, "generate/spec_admit",
                self._model, self._draft_model, self._params,
                self._draft_params, self.state, jnp.int32(row), prompt_row,
                key, self._temperature, _shape=P, **self._kw(),
            )

    def prefill_handoff(self, prompt_row, *, key=None) -> "KVHandoff":
        """Run ONE request's prefill at batch 1 and package the result as
        a :class:`KVHandoff` — the EXPORT half of the prefill/decode lane
        split.  Works on an un-started batcher (a dedicated prefill
        replica never calls :meth:`start`); the live decode batch is
        untouched.

        Key discipline: the admit counter advances and derives the row
        key exactly like :meth:`admit`, so a prefill-lane batcher owns
        its own key stream.  Greedy decoding (``sampled=False``) never
        consumes the key, so a handed-off row is bit-identical to a
        local :meth:`admit` of the same prompt on the decode replica —
        the fleet bit-equality contract.  Sampled handoffs need the
        caller to coordinate keys across lanes via ``key=``.
        """
        self._refuse_handoff("prefill_handoff()")
        prompt_row = jnp.asarray(prompt_row, jnp.int32)
        if prompt_row.ndim == 1:
            prompt_row = prompt_row[None, :]
        if prompt_row.ndim != 2 or prompt_row.shape[0] != 1 \
                or prompt_row.shape[1] < 1:
            raise ValueError(
                f"prefill_handoff() needs a single non-empty prompt row "
                f"([P] or [1, P]), got shape "
                f"{tuple(jnp.asarray(prompt_row).shape)}"
            )
        P = prompt_row.shape[1]
        if P + 1 > self.total_len:
            raise ValueError(
                f"prompt length {P} + 1 exceeds total_len "
                f"({self.total_len})"
            )
        if key is None:
            self._admits += 1
            key = jax.random.fold_in(self._rng, self._admits)
        state1 = ledger_call(
            _spec_prefill, "generate/spec_prefill",
            self._model, self._draft_model, self._params,
            self._draft_params, prompt_row, key, self._temperature,
            max_new_tokens=self.total_len - P, **self._kw(),
        )
        return export_kv_row(state1, 0)

    @property
    def prefix_cache_ok(self) -> bool:
        """Whether rows can be rebuilt from imported prefix pages: the
        page index assumes the position==slot cache layout, and a
        rolling cache remaps slots mod the window — its pages are not
        content-addressable by token prefix.  Nor can pages carry a
        latent cache or a hidden-state draft's state yet."""
        return self._movable() and not any(
            getattr(m.config, "decode_rolling_cache", False)
            for m in (self._model, self._draft_model)
        )

    def prefill_suffix_handoff(self, prompt_row, prefix: "KVHandoff", *,
                               key=None) -> "KVHandoff":
        """Prefill ONLY the uncached suffix of ``prompt_row`` on top of
        a prefix-shaped handoff (:meth:`KVHandoff.from_pages`) and
        package the complete row as a :class:`KVHandoff` — the
        prefix-cache admission path: cached pages import as data, the
        suffix pays the only model forward.  Greedy output is bit-equal
        to :meth:`prefill_handoff` of the full prompt (the kvstore
        oracle); the admit counter advances exactly like
        :meth:`prefill_handoff`, so key discipline is unchanged."""
        self._refuse_handoff("prefill_suffix_handoff()")
        prompt_row = jnp.asarray(prompt_row, jnp.int32)
        if prompt_row.ndim == 1:
            prompt_row = prompt_row[None, :]
        if prompt_row.ndim != 2 or prompt_row.shape[0] != 1 \
                or prompt_row.shape[1] < 1:
            raise ValueError(
                f"prefill_suffix_handoff() needs a single non-empty "
                f"prompt row ([P] or [1, P]), got shape "
                f"{tuple(jnp.asarray(prompt_row).shape)}"
            )
        if not self.prefix_cache_ok:
            raise ValueError(
                "prefix-cache import needs the position==slot cache "
                "layout; a decode_rolling_cache model remaps slots"
            )
        P = prompt_row.shape[1]
        if P + 1 > self.total_len:
            raise ValueError(
                f"prompt length {P} + 1 exceeds total_len "
                f"({self.total_len})"
            )
        C = int(np.asarray(prefix.n_tok)[0])
        if not 0 < C < P:
            raise ValueError(
                f"cached prefix must cover 1..P-1 tokens, got {C} of "
                f"{P} (the final position's logits must be recomputed)"
            )
        pfx = np.asarray(prefix.buf)[0, :C]
        if not np.array_equal(pfx, np.asarray(prompt_row)[0, :C]):
            raise ValueError(
                f"prefix handoff tokens do not match the prompt's first "
                f"{C} tokens — wrong store entry (hash collision or a "
                f"mixed-up session)"
            )
        if key is None:
            self._admits += 1
            key = jax.random.fold_in(self._rng, self._admits)
        suffix = prompt_row[:, C:]
        state1 = ledger_call(
            _spec_suffix_prefill, "generate/spec_suffix_prefill",
            self._model, self._draft_model, self._params,
            self._draft_params, prompt_row, suffix, jnp.int32(C),
            prefix.cache_t, prefix.cache_d, key, self._temperature,
            max_new_tokens=self.total_len - P, **self._kw(),
        )
        return export_kv_row(state1, 0)

    def prefill_from_pages(self, prompt_row, pages, *,
                           key=None) -> "KVHandoff":
        """Convenience over :meth:`prefill_suffix_handoff`: reassemble
        ``pages`` with THIS batcher's slot layout
        (:meth:`KVHandoff.from_pages`) and run the suffix prefill."""
        self._refuse_handoff("prefill_from_pages()")
        prefix = KVHandoff.from_pages(
            pages, total_len=self.total_len,
            slots_t=int(self._model.config.max_seq),
            slots_d=int(self._draft_model.config.max_seq),
        )
        return self.prefill_suffix_handoff(prompt_row, prefix, key=key)

    def admit_prefilled(self, row: int, handoff: "KVHandoff", *,
                        preempt: bool = False) -> None:
        """Import a :class:`KVHandoff` into row ``row`` — the decode-lane
        counterpart of :meth:`admit` minus the prefill: a cheap scatter
        dispatch, so long prompts prefilled elsewhere never stall the
        decode rounds here.  Same occupancy rules as :meth:`admit`."""
        self._refuse_handoff("admit_prefilled()")
        if self.state is None:
            raise ValueError("call start() before admit_prefilled()")
        B = self.state[0].shape[0]
        if not 0 <= row < B:
            raise ValueError(
                f"admit_prefilled() row {row} out of range for batch of "
                f"{B} rows"
            )
        if not preempt and not bool(self.reads(self.state[2], "done")[row]):
            raise ValueError(
                f"admit_prefilled() into row {row} which is still "
                f"decoding — harvest it first (done flag unset), or pass "
                f"preempt=True to drop its occupant deliberately"
            )
        if int(handoff.total_len) != self.total_len:
            raise ValueError(
                f"handoff total_len ({handoff.total_len}) != this "
                f"batcher's total_len ({self.total_len}); prefill and "
                f"decode lanes must share the buffer layout"
            )
        self.state = ledger_call(
            _spec_import_row, "generate/spec_import_row",
            self.state, jnp.int32(row), handoff.buf, handoff.n_tok,
            handoff.done, handoff.cache_t, handoff.cache_d,
        )

    def retire(self, row: int) -> None:
        """Mark a row done without admitting a replacement — its slot
        idles (the round body skips done rows) until the next admit."""
        if self.state is None:
            raise ValueError("call start() before retire()")
        if not 0 <= row < self.state[0].shape[0]:
            raise ValueError(
                f"retire() row {row} out of range for batch of "
                f"{self.state[0].shape[0]} rows"
            )
        state = self.state
        self.state = state[:2] + (state[2].at[row].set(True),) + state[3:]

    def finished_rows(self):
        """Row indices whose requests are complete (eos or full buffer)."""
        if self.state is None:
            return []
        return [int(r) for r in
                np.nonzero(self.reads(self.state[2], "done"))[0]]

    @property
    def all_done(self) -> bool:
        return self.state is not None and bool(np.all(
            self.reads(self.state[2], "done")))

    def row_tokens(self, row: int):
        """``(tokens [total_len], n_tok)`` for one row, eos-tail-filled
        to the fixed-length contract of the one-dispatch path."""
        if self.state is None:
            raise ValueError("call start() before row_tokens()")
        buf, n_tok = self.state[0], self.state[1]
        filled = _spec_eos_fill(buf, n_tok, self.eos_token)
        return (self.reads(filled[row], "row_tokens"),
                int(self.reads(n_tok[row], "row_n_tok")))

    def stats(self):
        """``{"rounds": int, "drafted": [B], "accepted": [B]}`` — same
        shape as the one-dispatch ``return_stats`` payload.  Per-row
        counters reset when a row is re-admitted."""
        if self.state is None:
            raise ValueError("call start() before stats()")
        self.publish_counters()
        rounds, drafted, accepted = self.state[6]
        return {"rounds": int(rounds), "drafted": np.asarray(drafted),
                "accepted": np.asarray(accepted)}

    def publish_counters(self) -> None:
        """Fetch the totals the rounds accumulated on the device (the round
        of a hidden-state draft, or of models with experts or a selection,
        keeps them: tokens each held expert got, routed and held slots,
        drafted and accepted, keys kept and seen), add them to the
        process-wide record (:func:`rocket_tpu.observe.trace.get_rounds`)
        and start them again from nought.  One device-to-host read, made
        when someone asks — :meth:`stats`, a closing ``ServingLoop`` — and
        never by a round."""
        if self.state is None or not isinstance(self.state[-1], dict):
            return
        from rocket_tpu.observe.trace import get_rounds

        get_rounds().add(_narrow(jax.device_get(self.state[-1])))
        self.state = self.state[:-1] + (jax.tree_util.tree_map(
            jnp.zeros_like, self.state[-1]),)


@functools.partial(jax.jit, static_argnums=0, static_argnames=("temperature",))
def _chunk_probs(model, params, cache, toks, pos0, *, temperature=1.0):
    """Like :func:`_chunk_step` but returns the full next-token
    probability rows ([1, S, V], f32 softmax at ``temperature``) instead
    of argmaxes — the speculative-SAMPLING verifier needs p and q."""
    S = toks.shape[1]
    positions = pos0 + jnp.arange(S, dtype=jnp.int32)[None, :]
    out, mutated = model.apply(
        {"params": params, "cache": cache},
        {"tokens": toks, "positions": positions},
        decode=True, mutable=["cache"],
    )
    probs = jax.nn.softmax(
        out["logits"].astype(jnp.float32) / temperature, axis=-1
    )
    return mutated["cache"], probs


def _norm_row(row: "np.ndarray") -> "np.ndarray":
    """Renormalize an f32 softmax row in float64 for numpy's choice()."""
    row = np.asarray(row, np.float64)
    return row / row.sum()


def speculative_sample(
    model: Any,
    params: Any,
    draft_model: Any,
    draft_params: Any,
    prompt: jax.Array,
    max_new_tokens: int,
    n_draft: int = 4,
    temperature: float = 1.0,
    seed: int = 0,
    return_stats: bool = False,
    eos_token: Optional[int] = None,
) -> Any:
    """Speculative SAMPLING (rejection-based): like
    :func:`speculative_generate` but for ``temperature > 0`` — the draft
    proposes from its own distribution q, the target verifies the block
    in one forward, and each proposal is accepted with probability
    ``min(1, p/q)``; a rejection resamples from ``max(0, p - q)``.  The
    emitted tokens are distributed EXACTLY according to the target's
    sampling distribution p, whatever the draft is
    (:func:`_accept_resample` carries the math and its distributional
    test).  Batch must be 1; acceptance randomness runs on the host
    (``numpy`` generator seeded by ``seed``), so a fixed seed gives a
    reproducible trace.  Shares :func:`_speculative_loop`'s frontier /
    eos / stats machinery with the greedy variant.
    """
    if temperature <= 0.0:
        raise ValueError(
            "speculative_sample needs temperature > 0; use "
            "speculative_generate for greedy decoding"
        )
    host = np.random.default_rng(seed)
    target_step = functools.partial(
        _chunk_probs, model, params, temperature=temperature
    )
    draft_step = functools.partial(
        _chunk_probs, draft_model, draft_params, temperature=temperature
    )
    caches = {}

    def prefill():
        # _prefill_cache chunks rolling-cache prompts by their slack;
        # softmax over the last-position row matches _chunk_probs' slice
        caches["t"], last = _prefill_cache(model, params, prompt)
        caches["d"], _ = _prefill_cache(draft_model, draft_params, prompt)
        row = _norm_row(np.asarray(
            jax.nn.softmax(last[0] / temperature)
        ))
        return int(host.choice(row.shape[0], p=row))

    def do_round(feed_toks, feed_start, pending, pos, k):
        feed = jnp.asarray(feed_toks, jnp.int32)[None, :]
        caches["d"], d_probs = draft_step(caches["d"], feed, feed_start)
        dp = feed_start + len(feed_toks)
        q_rows = [np.asarray(d_probs[0, -1])]
        V = q_rows[0].shape[0]
        drafts = [int(host.choice(V, p=_norm_row(q_rows[0])))]
        for _ in range(k - 1):
            caches["d"], d_probs = draft_step(
                caches["d"], jnp.asarray([[drafts[-1]]], jnp.int32), dp
            )
            dp += 1
            q_rows.append(np.asarray(d_probs[0, -1]))
            drafts.append(int(host.choice(V, p=_norm_row(q_rows[-1]))))

        chunk = jnp.asarray([[pending] + drafts], jnp.int32)
        caches["t"], t_probs = target_step(caches["t"], chunk, pos)
        p_rows = np.asarray(t_probs[0])  # [k+1, V] — every row is needed
        j, tok = _accept_resample(
            p_rows, np.stack(q_rows), np.asarray(drafts), host
        )
        return drafts, tok, j

    def rewind(pos, d_pos):
        caches["t"] = _set_cache_index(caches["t"], pos)
        caches["d"] = _set_cache_index(caches["d"], d_pos)

    return _speculative_loop(
        "speculative_sample", model, draft_model, prompt, max_new_tokens,
        n_draft, return_stats, eos_token, prefill, do_round, rewind,
    )


def _accept_resample(p_rows: "np.ndarray", q_rows: "np.ndarray",
                     drafts: "np.ndarray", rng: "np.random.Generator"):
    """The speculative-SAMPLING core (host-side, pure numpy).

    Given the target's next-token distributions ``p_rows`` ([k+1, V]:
    row i is the target dist AFTER the i-th chunk token), the draft's
    distributions ``q_rows`` ([k, V]) and its proposals ``drafts``
    ([k]), returns ``(j, token)``: ``j`` accepted proposals and the
    round's final emitted token — a rejection-resample from
    ``max(0, p - q)`` at the first rejection, or a bonus sample from
    ``p_rows[k]`` when everything is accepted.

    This is the standard speculative-sampling rule: accept ``d_i`` with
    probability ``min(1, p(d_i)/q(d_i))``; the combined emitted-token
    distribution is EXACTLY ``p`` regardless of ``q`` (unit-tested
    distributionally in ``tests/test_models.py``).
    """
    k = drafts.shape[0]
    V = p_rows.shape[1]
    for i in range(k):
        d = int(drafts[i])
        p_d = float(p_rows[i, d])
        q_d = float(q_rows[i, d])
        # q_d == 0 cannot happen for a token actually sampled from q;
        # treat it as a rejection rather than dividing by zero
        if q_d > 0.0 and rng.random() < min(1.0, p_d / q_d):
            continue
        residual = np.maximum(
            np.asarray(p_rows[i], np.float64)
            - np.asarray(q_rows[i], np.float64),
            0.0,
        )
        total = float(residual.sum())
        probs = residual / total if total > 0.0 else _norm_row(p_rows[i])
        return i, int(rng.choice(V, p=probs))
    # all k accepted: bonus token straight from the target
    return k, int(rng.choice(V, p=_norm_row(p_rows[k])))


def _validate_beam_lm(model, P, max_new_tokens, beam_size):
    """Shared loud validation for the decoder-only beam entry points."""
    if _keeps_state(model):
        raise ValueError(
            "beam search cannot run state-space layers (mamba) yet: the "
            "beam gather moves cache slots, not a recurrent state")
    if _latent(model):
        raise ValueError(
            "beam search cannot run a latent-attention (mla) model yet: "
            "its cache is written at each row's positions, which the beam "
            "gather does not track")
    if _selects(model):
        raise ValueError(
            "beam search cannot run a model whose attention chooses its "
            "keys (select) yet: the beam gather has not been shown to carry "
            "the indexer's cache")
    if not model.config.causal:
        raise ValueError(
            "beam search requires a causal decoder "
            "(model.config.causal=True): with bidirectional attention the "
            "still-pad tail of the static buffer leaks into the frontier "
            "logits and the search silently returns garbage"
        )
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if beam_size < 1:
        raise ValueError(f"beam_size must be >= 1, got {beam_size}")
    total = P + max_new_tokens
    if total > model.config.max_seq:
        raise ValueError(
            f"prompt ({P}) + max_new_tokens ({max_new_tokens}) = {total} "
            f"exceeds config.max_seq ({model.config.max_seq})"
        )
    return total


def _beam_buf(prompt, beam_size, max_new_tokens, pad_id):
    """``[B, K, P + T]`` token buffer: prompt tiled beam-wise, pad tail."""
    B, P = prompt.shape
    buf = jnp.broadcast_to(prompt[:, None], (B, beam_size, P))
    return jnp.concatenate(
        [buf, jnp.full((B, beam_size, max_new_tokens), pad_id, jnp.int32)],
        axis=2,
    )


def beam_search(
    model: Any,
    params: Any,
    prompt: jax.Array,
    max_new_tokens: int,
    eos_id: int,
    beam_size: int = 4,
    length_penalty: float = 0.6,
    pad_id: int = 0,
) -> tuple:
    """Beam search for the decoder-only family (static shapes).

    The causal-LM counterpart of :func:`beam_search_seq2seq`: K beams
    per row decode over a ``[B*K, P+T]`` buffer with the same O(T)
    re-decode strategy (every step re-runs the full forward and reads
    the frontier logits — causal attention guarantees the still-``pad``
    tail cannot influence it; zero cache plumbing, beams reorder by a
    gather on the token buffer alone).  Finished beams (emitted
    ``eos_id``) freeze with a single ``pad_id`` continuation at
    unchanged score; final ranking uses the GNMT length penalty
    ``((5 + len) / 6) ** length_penalty``.

    This is the serving path's bit-equality ORACLE: each step pays a
    full ``P + T``-long forward, so it is O(T) full re-decodes.
    :func:`beam_search_cached` produces the same tokens from one prompt
    prefill plus O(T) single-token cached forwards — use that for
    serving and this for verification.

    Returns ``(tokens [B, P + T], scores [B])`` — the best beam per row
    and its length-normalized log-probability.  ``beam_size=1``
    reproduces greedy :func:`generate` decoding (tested).
    """
    B, P = prompt.shape
    K = beam_size
    _validate_beam_lm(model, P, max_new_tokens, K)
    buf = _beam_buf(prompt, K, max_new_tokens, pad_id)

    def frontier_logits(flat_buf, t):
        out = model.apply(
            {"params": params}, {"tokens": flat_buf}, train=False
        )
        return jax.lax.dynamic_slice_in_dim(
            out["logits"], P - 1 + t, 1, axis=1
        )[:, 0]

    return _beam_loop(frontier_logits, buf, P, max_new_tokens,
                      eos_id, pad_id, length_penalty)


def beam_search_cached(
    model: Any,
    params: Any,
    prompt: jax.Array,
    max_new_tokens: int,
    eos_id: int,
    beam_size: int = 4,
    length_penalty: float = 0.6,
    pad_id: int = 0,
) -> tuple:
    """KV-cached beam search — same results as :func:`beam_search`,
    O(T) single-token forwards instead of O(T) full re-decodes.

    All K beams share ONE prompt prefill (:func:`_chunked_prefill` at
    batch ``B``; the cache is tiled beam-wise afterwards, so the prompt
    is never recomputed per beam).  Each subsequent step runs a single
    cached forward over the ``[B*K, 1]`` frontier tokens, expands with
    the shared :func:`_beam_expand` machinery, and reorders the K/V
    cache rows with the SAME ``src_beam`` gather that reorders the token
    buffer — a beam that survives carries its cache history with it.
    Frozen (eos) beams keep decoding their ``pad_id`` continuations into
    the cache exactly as the oracle's buffer holds them, so the visible
    prefix — and therefore every logit — matches the re-decode path.

    Decode work per output token drops from one ``P + T``-long forward
    to one single-token forward: the prompt's K/V are computed once and
    read T times, which is the whole point of serving from a cache
    (decode is bandwidth-bound).

    Returns ``(tokens [B, P + T], scores [B])``, matching
    :func:`beam_search` on the same inputs (tested bit-for-bit on the
    seed oracles).
    """
    B, P = prompt.shape
    K = beam_size
    _validate_beam_lm(model, P, max_new_tokens, K)
    buf = _beam_buf(prompt, K, max_new_tokens, pad_id)
    V = model.config.vocab_size

    # ONE prefill at batch B; every beam then shares its row's prompt K/V
    cache, last = _chunked_prefill(
        model, params, zero_cache(model, params, prompt), prompt
    )
    # tile [B, slots, KV, D] -> [B*K, ...] matching buf.reshape(B*K, ...)
    # row order; the scalar cache_index stays shared (uniform frontiers)
    cache = jax.tree_util.tree_map_with_path(
        lambda p, a: jnp.repeat(a, K, axis=0) if _is_cache_payload(p, a)
        else a,
        cache,
    )
    row0 = jnp.arange(B, dtype=jnp.int32)[:, None] * K  # [B, 1]

    def gather_cache(cache, src_beam):
        flat = (row0 + src_beam).reshape(-1)
        return jax.tree_util.tree_map_with_path(
            lambda p, a: a[flat] if _is_cache_payload(p, a) else a, cache
        )

    scores = jnp.full((B, K), -jnp.inf).at[:, 0].set(0.0)
    finished = jnp.zeros((B, K), bool)
    lengths = jnp.zeros((B, K), jnp.int32)

    # step 0 expands straight from the prefill's frontier logits — the
    # oracle's t=0 full forward reads the same position-(P-1) logits
    logits0 = jnp.broadcast_to(last[:, None], (B, K, V)).reshape(B * K, V)
    buf, scores, finished, lengths, src0 = _beam_expand(
        logits0, buf, scores, finished, lengths, P, eos_id, pad_id
    )
    cache = gather_cache(cache, src0)

    def step(carry, t):
        cache, buf, scores, finished, lengths = carry
        # feed the token written at P+t-1; the scalar cache frontier is
        # already P+t-1, so the single-token write lands in its slot
        tok = jax.lax.dynamic_slice_in_dim(
            buf, P + t - 1, 1, axis=2
        ).reshape(B * K, 1)
        pos = jnp.broadcast_to(
            jnp.asarray(P - 1 + t, jnp.int32)[None, None], (B * K, 1)
        )
        out, mutated = model.apply(
            {"params": params, "cache": cache},
            {"tokens": tok, "positions": pos},
            decode=True, mutable=["cache"],
        )
        buf, scores, finished, lengths, src_beam = _beam_expand(
            out["logits"][:, 0], buf, scores, finished, lengths, P + t,
            eos_id, pad_id,
        )
        cache = gather_cache(mutated["cache"], src_beam)
        return (cache, buf, scores, finished, lengths), None

    (cache, buf, scores, finished, lengths), _ = jax.lax.scan(
        step, (cache, buf, scores, finished, lengths),
        jnp.arange(1, max_new_tokens),
    )
    return _beam_finalize(buf, scores, lengths, length_penalty)


def _beam_expand(logits_t, buf, scores, finished, lengths, write_pos,
                 eos_id, pad_id):
    """One beam-expansion step, shared by every beam variant: K*V top-k
    over ``scores + log_softmax(logits_t)`` with frozen-beam pad
    continuations, gather of the per-beam state by the winning source
    beams, frontier token write at ``write_pos``, and eos/length
    accounting.  ``logits_t`` is ``[B*K, V]``.  Returns ``(buf, scores,
    finished, lengths, src_beam)`` — ``src_beam [B, K]`` so cached
    variants can reorder their K/V rows with the same gather."""
    B, K, total = buf.shape
    V = logits_t.shape[-1]
    logp = jax.nn.log_softmax(
        logits_t.astype(jnp.float32), axis=-1
    ).reshape(B, K, V)
    # finished beams: only the pad continuation, at unchanged score
    frozen = jnp.full((V,), -jnp.inf).at[pad_id].set(0.0)
    logp = jnp.where(finished[:, :, None], frozen[None, None], logp)
    cand = scores[:, :, None] + logp  # [B, K, V]
    top_scores, top_idx = jax.lax.top_k(cand.reshape(B, K * V), K)
    src_beam = top_idx // V  # which beam each winner extends
    token = (top_idx % V).astype(jnp.int32)
    buf = jnp.take_along_axis(buf, src_beam[:, :, None], axis=1)
    finished = jnp.take_along_axis(finished, src_beam, axis=1)
    lengths = jnp.take_along_axis(lengths, src_beam, axis=1)
    buf = jax.lax.dynamic_update_slice_in_dim(
        buf, token[:, :, None], write_pos, axis=2
    )
    lengths = jnp.where(finished, lengths, lengths + 1)
    finished = finished | (token == eos_id)
    return buf, top_scores, finished, lengths, src_beam


def _beam_finalize(buf, scores, lengths, length_penalty):
    """GNMT length-normalized ranking; best beam per row."""
    norm = ((5.0 + lengths.astype(jnp.float32)) / 6.0) ** length_penalty
    final = scores / norm
    best = jnp.argmax(final, axis=1)
    tokens = jnp.take_along_axis(buf, best[:, None, None], axis=1)[:, 0]
    return tokens, jnp.take_along_axis(final, best[:, None], axis=1)[:, 0]


def _beam_loop(frontier_logits, buf, write_at, max_new_tokens,
               eos_id, pad_id, length_penalty):
    """Shared re-decode beam machinery (:func:`beam_search`,
    :func:`beam_search_seq2seq`): drives :func:`_beam_expand` with each
    step's full-forward frontier logits.  ``frontier_logits (flat_buf
    [B*K, total], t) -> [B*K, V]`` supplies each step's next-token
    logits; ``write_at`` is the buffer index of the first generated slot
    (seq2seq: 1 past BOS; LM: the prompt length).  ``buf`` is ``[B, K,
    total]`` with the prompt/BOS prefix in place.  Returns ``(tokens
    [B, total], scores [B])`` — best beam per row."""
    B, K, total = buf.shape
    # all beams start identical: beam 0 live at 0.0, the rest at -inf so
    # the first expansion seeds K DISTINCT continuations
    scores = jnp.full((B, K), -jnp.inf).at[:, 0].set(0.0)
    finished = jnp.zeros((B, K), bool)
    lengths = jnp.zeros((B, K), jnp.int32)  # generated tokens incl. eos

    def step(carry, t):
        buf, scores, finished, lengths = carry
        logits_t = frontier_logits(buf.reshape(B * K, total), t)
        buf, scores, finished, lengths, _ = _beam_expand(
            logits_t, buf, scores, finished, lengths, write_at + t,
            eos_id, pad_id,
        )
        return (buf, scores, finished, lengths), None

    (buf, scores, finished, lengths), _ = jax.lax.scan(
        step, (buf, scores, finished, lengths),
        jnp.arange(max_new_tokens),
    )
    return _beam_finalize(buf, scores, lengths, length_penalty)


def _seq2seq_prepare(model, params, inputs, inputs_mask, max_new_tokens):
    """Shared seq2seq decode setup: length validation (incl. the
    learned-positions encoder guard), params normalization, one encoder
    pass.  Returns ``(variables, memory, total)``."""
    total = 1 + max_new_tokens
    if total > model.config.max_seq:
        raise ValueError(
            f"1 + max_new_tokens = {total} exceeds max_seq "
            f"{model.config.max_seq}"
        )
    if (
        model.config.positions == "learned"
        and inputs.shape[1] > model.config.max_seq
    ):
        # Learned positions only have max_seq table rows: the encoder
        # would die in a confusing (1, max_seq, H)-vs-(B, S, H) broadcast
        # error — fail with the actual cause instead.  RoPE computes
        # positions on the fly and handles longer inputs (extrapolated).
        raise ValueError(
            f"encoder inputs length {inputs.shape[1]} exceeds max_seq "
            f"{model.config.max_seq} (learned position table size)"
        )
    variables = params if "params" in params else {"params": params}
    memory = model.apply(
        variables, inputs, inputs_mask, False, method="encode"
    )
    return variables, memory, total


def generate_seq2seq(
    model: Any,
    params: Any,
    inputs: jax.Array,
    max_new_tokens: int,
    bos_id: int,
    inputs_mask: Optional[jax.Array] = None,
    rng: Optional[jax.Array] = None,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    pad_id: int = 0,
) -> jax.Array:
    """Autoregressive decoding for the encoder-decoder family.

    The encoder runs ONCE (``model.apply(..., method='encode')``); the
    decoder then re-runs over a static ``[B, 1 + max_new_tokens]`` target
    buffer inside a ``lax.scan``, reading the logits at the frontier each
    step — causal self-attention guarantees positions beyond the frontier
    (still ``pad_id``) cannot influence it.  Static shapes throughout, so
    the loop compiles once; the O(T) re-decode trades peak efficiency for
    zero cache plumbing, the right call at seq2seq output lengths.

    Returns ``[B, 1 + max_new_tokens]`` tokens (BOS first).
    """
    B = inputs.shape[0]
    variables, memory, total = _seq2seq_prepare(
        model, params, inputs, inputs_mask, max_new_tokens
    )
    if rng is None:
        rng = jax.random.PRNGKey(0)
    buf = jnp.full((B, total), pad_id, jnp.int32).at[:, 0].set(bos_id)

    def step(carry, t):
        buf, rng = carry
        logits = model.apply(
            variables, buf, memory, inputs_mask, False, method="decode"
        )
        logits_t = jax.lax.dynamic_slice_in_dim(logits, t, 1, axis=1)[:, 0]
        rng, sub = jax.random.split(rng)
        nxt = _sample(logits_t, sub, temperature, top_k, top_p)
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, nxt[:, None], t + 1, axis=1
        )
        return (buf, rng), None

    (buf, _), _ = jax.lax.scan(
        step, (buf, rng), jnp.arange(max_new_tokens)
    )
    return buf


def beam_search_seq2seq(
    model: Any,
    params: Any,
    inputs: jax.Array,
    max_new_tokens: int,
    bos_id: int,
    eos_id: int,
    beam_size: int = 4,
    inputs_mask: Optional[jax.Array] = None,
    length_penalty: float = 0.6,
    pad_id: int = 0,
) -> tuple:
    """Beam search for the encoder-decoder family (static shapes).

    Encode once; K beams per row decode over a ``[B*K, 1+T]`` buffer with
    the same O(T) re-decode as :func:`generate_seq2seq`.  Per step the
    ``[B, K, V]`` continuation scores reduce with ``lax.top_k`` over the
    flattened ``K*V`` candidates; finished beams (emitted ``eos_id``) are
    frozen — they carry exactly one ``pad_id`` continuation at unchanged
    score, so they stay comparable in the same top-k.  Final ranking uses
    the GNMT length penalty ``((5 + len) / 6) ** length_penalty``.

    Returns ``(tokens [B, 1+T], scores [B])`` — the best beam per row and
    its length-normalized log-probability.
    """
    B = inputs.shape[0]
    K = beam_size
    variables, memory, total = _seq2seq_prepare(
        model, params, inputs, inputs_mask, max_new_tokens
    )
    # tile encoder outputs beam-wise: [B, ...] -> [B*K, ...]
    tiled_memory = jax.tree_util.tree_map(
        lambda x: jnp.repeat(x, K, axis=0), memory
    )
    tiled_mask = (
        jnp.repeat(inputs_mask, K, axis=0) if inputs_mask is not None
        else None
    )

    buf = jnp.full((B, K, total), pad_id, jnp.int32).at[:, :, 0].set(bos_id)

    def frontier_logits(flat_buf, t):
        logits = model.apply(
            variables, flat_buf, tiled_memory, tiled_mask, False,
            method="decode",
        )
        return jax.lax.dynamic_slice_in_dim(logits, t, 1, axis=1)[:, 0]

    return _beam_loop(frontier_logits, buf, 1, max_new_tokens,
                      eos_id, pad_id, length_penalty)
