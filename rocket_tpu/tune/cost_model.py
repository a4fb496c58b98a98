"""Analytical roofline cost model — the search's seed ranking.

Owns the device-peak tables and the GPT-2 analytical step-FLOPs formula
that ``bench.py`` reports MFU against (bench imports them from here, so
the autotuner and the ladder always agree on the accounting), plus an
HBM-bytes model per tune point.  The predicted step time is the roofline
``max(flops / peak_flops, bytes / peak_bw)``.

The byte model is a documented RANKING heuristic, not a simulator: it
captures the first-order effects each knob has on traffic (remat trades
activation bytes for recompute FLOPs, ``fused_ce`` deletes the
``[B*S, vocab]`` logits round-trip, bf16 Adam moments shrink two of the
optimizer passes, donation spares a params-sized copy) so the seeded
search probes the plausible region first.  Measured probes, not the
model, pick the winner.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

# Published per-chip peaks (Google Cloud TPU documentation, the page of
# each generation: bf16 FLOP/s and HBM bytes/s).  More specific kinds
# ('v5 lite', 'v5p') must precede bare 'v5' — dicts preserve insertion
# order.  A kind that matches no key is an error, never a default: a CPU
# run gets no MFU/MBU at all rather than one over some chip's peaks.
PEAK_FLOPS_BY_KIND: Dict[str, float] = {
    "v5 lite": 197e12, "v5e": 197e12,
    "v4": 275e12,
    "v5p": 459e12, "v5": 459e12,
    "v6 lite": 918e12, "v6e": 918e12,
    "v3": 123e12,
    "v2": 45e12,
}

# HBM bandwidth peak (bytes/s) per chip — what decode MBU is quoted over.
PEAK_HBM_BY_KIND: Dict[str, float] = {
    "v5 lite": 819e9, "v5e": 819e9,
    "v4": 1228e9,
    "v5p": 2765e9, "v5": 2765e9,
    "v6 lite": 1640e9, "v6e": 1640e9,
    "v3": 900e9,
    "v2": 700e9,
}


def _local_device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


def _peak(table: Dict[str, float], device_kind: Optional[str]) -> float:
    kind = device_kind if device_kind is not None else _local_device_kind()
    for key, val in table.items():
        if key in kind.lower():
            return val
    raise ValueError(
        f"no published peak for device kind {kind!r}; known kinds: "
        f"{sorted(table)} — pass the device_kind of the chip being "
        f"modelled, or add its peaks with their source"
    )


def device_peak_flops(device_kind: Optional[str] = None) -> float:
    """bf16 peak for ``device_kind`` (default: the local accelerator).
    Raises ``ValueError`` naming a kind the table does not hold."""
    return _peak(PEAK_FLOPS_BY_KIND, device_kind)


def device_peak_hbm_bytes(device_kind: Optional[str] = None) -> float:
    """HBM bandwidth peak for ``device_kind`` (default: local).  Raises
    ``ValueError`` naming a kind the table does not hold."""
    return _peak(PEAK_HBM_BY_KIND, device_kind)


def gpt2_step_flops(cfg: Any, batch: int, seq: int) -> float:
    """Training-step model FLOPs: 6 * params * tokens + attention term.

    ``cfg`` is a ``TransformerConfig`` (duck-typed: vocab_size, hidden,
    max_seq, n_layers, mlp_dim, n_heads, head_dim, attention_window).
    """
    n_params = (
        cfg.vocab_size * cfg.hidden  # embed (tied head reuses it)
        # learned positions: pinned at the ladder's 1024 table regardless
        # of a long-seq point's larger max_seq — positions are a broadcast
        # add, not matmul work, so letting the term scale with max_seq
        # would inflate long-seq MFU by phantom FLOPs (it stays only for
        # comparability with the committed round-2/3/4 numbers, where it
        # is a fixed 0.6%)
        + min(cfg.max_seq, 1024) * cfg.hidden
        + cfg.n_layers * (
            4 * cfg.hidden * cfg.hidden  # qkvo
            + 2 * cfg.hidden * cfg.mlp_dim  # gelu mlp up+down
            + 4 * cfg.hidden  # norms + biases (negligible)
        )
    )
    tokens = batch * seq
    dense = 6.0 * n_params * tokens
    # attention scores+context: fwd 2*2*B*H*S^2*D, bwd ~2x.  The full-
    # causal convention (the committed r2-r4 numbers) stays untouched; a
    # sliding window attends W*S - W(W-1)/2 pairs instead of the causal
    # S(S+1)/2, so the term scales by that ratio — crediting the full
    # square would inflate windowed-point MFU by phantom FLOPs.
    attn = 3.0 * 2.0 * 2.0 * batch * cfg.n_heads * seq * seq * cfg.head_dim
    W = min(cfg.attention_window or seq, seq)
    if W < seq:
        attn *= (W * seq - W * (W - 1) / 2.0) / (seq * (seq + 1) / 2.0)
    return dense + attn


def _tune_param_count(t: Dict[str, Any]) -> float:
    hidden = int(t.get("hidden", 768))
    layers = int(t.get("n_layers", 12))
    vocab = int(t.get("vocab", 50304))
    seq = int(t.get("seq", 1024))
    mlp = 4 * hidden
    return (vocab * hidden + min(seq, 1024) * hidden
            + layers * (4 * hidden * hidden + 2 * hidden * mlp + 4 * hidden))


def tune_step_flops(t: Dict[str, Any]) -> float:
    """Analytical step FLOPs straight from a merged tune dict (the dict
    ``bench.bench_gpt2`` consumes), including the remat recompute tax:
    the canonical fwd:bwd split is 2N:4N tokens-FLOPs, so recomputing the
    forward (``remat_policy='nothing'``) adds 2N back (8/6 of baseline);
    ``'dots'`` keeps the matmul outputs and recomputes only cheap
    elementwise work (~6.5/6)."""
    batch = int(t.get("batch", 16))
    seq = int(t.get("seq", 1024))
    hidden = int(t.get("hidden", 768))
    heads = int(t.get("n_heads", 12))
    n = _tune_param_count(t)
    tokens = batch * seq
    dense = 6.0 * n * tokens
    attn = 12.0 * batch * heads * seq * seq * (hidden // max(heads, 1))
    W = t.get("window") or seq
    W = min(int(W), seq)
    if W < seq:
        attn *= (W * seq - W * (W - 1) / 2.0) / (seq * (seq + 1) / 2.0)
    total = dense + attn
    if t.get("remat"):
        policy = t.get("remat_policy", "nothing")
        total *= 8.0 / 6.0 if policy == "nothing" else 6.5 / 6.0
    return total


def tune_step_bytes(t: Dict[str, Any]) -> float:
    """First-order HBM traffic per train step for a merged tune dict.

    Accounted passes: bf16 params fwd + bwd read (2+2 B/param), the f32
    optimizer update (params read+write, two Adam moments read+write —
    the ``mu`` pair shrinks under ``mu_dtype='bf16'``), stored
    activations write+read (dropped under remat, ~60% kept under the
    'dots' policy), and the CE logits round-trip (``[B*S, vocab]`` f32
    write + read) unless ``fused_ce`` deletes it, in which case only a
    ``ce_chunk``-sized transient flows.  ``donate=False`` pays an extra
    params-sized copy; ``fused_qkv`` trims a small per-launch overhead.
    """
    batch = int(t.get("batch", 16))
    seq = int(t.get("seq", 1024))
    hidden = int(t.get("hidden", 768))
    layers = int(t.get("n_layers", 12))
    vocab = int(t.get("vocab", 50304))
    n = _tune_param_count(t)
    tokens = batch * seq

    param_bytes = n * 2.0 * (2 + 2)             # bf16 fwd + bwd reads
    mu_b = 2.0 if t.get("mu_dtype") == "bf16" else 4.0
    opt_bytes = n * (4.0 * 2 + mu_b * 2 + 4.0 * 2)  # p rw + mu rw + nu rw
    if t.get("donate") is False:
        opt_bytes += n * 4.0 * 2                # un-donated state copy

    # ~14 activation tensors of [B, S, hidden] width per block survive to
    # the backward pass when nothing is rematerialized (qkv, scores
    # context, mlp up, residuals, norms), written once and read once.
    act_per_layer = 14.0 * tokens * hidden * 2.0 * 2
    if t.get("remat"):
        policy = t.get("remat_policy", "nothing")
        act_per_layer *= 0.0 if policy == "nothing" else 0.6
    act_bytes = act_per_layer * layers
    if t.get("fused_qkv"):
        act_bytes *= 0.98                       # fewer launches/round-trips

    if t.get("fused_ce"):
        chunk = int(t.get("ce_chunk", 1024))
        logits_bytes = min(chunk, tokens) * vocab * 4.0 * 2
    else:
        logits_bytes = tokens * vocab * 4.0 * 2  # f32 write + bwd read
    return param_bytes + opt_bytes + act_bytes + logits_bytes


def predict_point(t: Dict[str, Any],
                  device_kind: Optional[str] = None) -> Dict[str, float]:
    """Roofline prediction for one tune point: ``{"flops", "bytes",
    "seconds", "tokens_per_s"}``.  ``seconds`` is the roofline max of the
    compute and bandwidth times — the seed-ranking scalar."""
    flops = tune_step_flops(t)
    nbytes = tune_step_bytes(t)
    secs = max(flops / device_peak_flops(device_kind),
               nbytes / device_peak_hbm_bytes(device_kind))
    tokens = int(t.get("batch", 16)) * int(t.get("seq", 1024))
    return {"flops": flops, "bytes": nbytes, "seconds": secs,
            "tokens_per_s": tokens / secs}
