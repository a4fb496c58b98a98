"""WarmupPlan: explicit AOT ``lower().compile()`` of the serving hot
path (ISSUE 15 tentpole, part 2).

The ledgered fixed-shape jit edges — ``generate/spec_round`` per
``n_draft`` and the ``generate/spec_prefill`` warm group — used to
compile lazily at first dispatch, inside the serving loop, after READY.
A :class:`WarmupPlan` derives the exact dispatch shapes from the batcher
config (``max_batch`` rows, the prompt-length-1 warm group
``ServingLoop._warm_start`` uses, the draft ladder) and compiles them
up front:

1. try :func:`~rocket_tpu.tune.compile_cache.load_aot` — a serialized
   executable from a previous process skips trace AND compile;
2. else ``lower().compile()`` — which hits the persistent compile cache
   on a warm host (compile served from disk) and populates it on a cold
   one, then :func:`~rocket_tpu.tune.compile_cache.save_aot` persists
   the executable where the backend supports serialization (graceful
   fall-through when not).

Either way the loop's own dispatch afterwards is cheap, and the whole
warmup is timed into the goodput ``compile`` bucket so a worker's READY
payload can report it.  Shape fidelity matters: the plan must reproduce
``_warm_start``'s ``zeros((max_batch, 1))`` group exactly or the AOT
work warms a cache line nobody reads.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from rocket_tpu.tune import compile_cache

logger = logging.getLogger("rocket_tpu.warmup")


@dataclasses.dataclass(frozen=True)
class WarmupPlan:
    """The shapes to pre-compile: one prefill at ``(max_batch,
    prompt_len)``, one spec round per entry in ``n_drafts``, and one
    ``generate/spec_admit`` per entry in ``prompt_lens`` (the admit edge
    is shape-polymorphic per prompt length by design — a deployment that
    knows its prompt lengths can pre-pay them so the first routed
    request never touches the backend compiler).  ``aot=False`` skips
    executable serialization (persistent cache still applies)."""

    max_batch: int
    prompt_len: int = 1
    n_drafts: Tuple[int, ...] = ()
    prompt_lens: Tuple[int, ...] = ()
    aot: bool = True

    def to_wire(self) -> Dict[str, Any]:
        return {"max_batch": self.max_batch, "prompt_len": self.prompt_len,
                "n_drafts": list(self.n_drafts),
                "prompt_lens": list(self.prompt_lens), "aot": self.aot}

    @classmethod
    def from_wire(cls, data: Dict[str, Any]) -> "WarmupPlan":
        return cls(max_batch=int(data["max_batch"]),
                   prompt_len=int(data.get("prompt_len", 1)),
                   n_drafts=tuple(int(n) for n in data.get("n_drafts", ())),
                   prompt_lens=tuple(
                       int(p) for p in data.get("prompt_lens", ())),
                   aot=bool(data.get("aot", True)))


def plan_for_batcher(bat: Any, max_batch: int,
                     *, extra_drafts: Tuple[int, ...] = (),
                     prompt_lens: Tuple[int, ...] = (),
                     aot: bool = True) -> WarmupPlan:
    """Derive the plan from a live :class:`ContinuousBatcher`: the
    configured ``n_draft`` plus the explicit ``extra_drafts``.
    ``prompt_lens`` rides through for deployments that know their
    request shapes (the admit edge is per-prompt-length)."""
    drafts = [int(bat.n_draft), *(int(n) for n in extra_drafts)]
    seen: Dict[int, None] = {}
    for n in drafts:
        if n > 0:
            seen.setdefault(n)
    return WarmupPlan(max_batch=int(max_batch), prompt_len=1,
                      n_drafts=tuple(seen),
                      prompt_lens=tuple(
                          int(p) for p in prompt_lens if int(p) > 0),
                      aot=aot)


def warm_batcher(bat: Any, plan: WarmupPlan) -> Dict[str, Any]:
    """Execute the plan against a batcher's models/params; returns
    ``{"compile_ms", "cache_hits", "edges", "aot_hits",
    "aot_serialized"}``.  Never raises — a failing edge is logged and
    skipped (the loop's inline ``expect_compile`` path still covers
    it)."""
    from rocket_tpu.models.generate import (
        _spec_admit,
        _spec_prefill,
        _spec_round,
    )
    from rocket_tpu.observe.ledger import get_goodput

    stats = {"compile_ms": 0.0, "cache_hits": 0, "edges": 0,
             "aot_hits": 0, "aot_serialized": 0}
    hits0 = compile_cache.hit_count()
    t0 = time.perf_counter()
    backend = jax.default_backend()
    ndev = len(jax.devices())
    with get_goodput().timed("compile"):
        prompt = jnp.zeros((plan.max_batch, plan.prompt_len), jnp.int32)
        prefill_args = (bat._model, bat._draft_model, bat._params,
                        bat._draft_params, prompt, bat._rng,
                        bat._temperature)
        prefill_kw = dict(
            max_new_tokens=bat.total_len - plan.prompt_len, **bat._kw())
        try:
            _spec_prefill.lower(*prefill_args, **prefill_kw).compile()
            stats["edges"] += 1
            # the round state's shape tree, without running the prefill
            state_sds = _spec_prefill.eval_shape(*prefill_args, **prefill_kw)
        except Exception:
            logger.warning("warmup: prefill lowering failed; loop will "
                           "compile inline", exc_info=True)
            stats["compile_ms"] = (time.perf_counter() - t0) * 1e3
            stats["cache_hits"] = compile_cache.hit_count() - hits0
            return stats
        for n_draft in plan.n_drafts:
            key = compile_cache.aot_key(
                "generate/spec_round", batch=plan.max_batch,
                total_len=bat.total_len, n_draft=n_draft, backend=backend,
                devices=ndev)
            if plan.aot and compile_cache.load_aot(key) is not None:
                # a previous process serialized this executable; its
                # lower().compile() also populated the persistent cache,
                # so the loop's dispatch stays a disk hit.
                stats["aot_hits"] += 1
                stats["edges"] += 1
                continue
            try:
                compiled = _spec_round.lower(
                    bat._model, bat._draft_model, bat._params,
                    bat._draft_params, state_sds, bat._temperature,
                    n_draft=n_draft, **bat._kw()).compile()
                stats["edges"] += 1
            except Exception:
                logger.warning("warmup: spec_round(n_draft=%d) lowering "
                               "failed", n_draft, exc_info=True)
                continue
            if plan.aot and compile_cache.save_aot(key, compiled):
                stats["aot_serialized"] += 1
        # Admit edges: SDS stand-ins for the traced args the batcher's
        # admit() passes (row index, one prompt row, a folded PRNG key),
        # so the lowered signature matches the live dispatch exactly.
        for p_len in plan.prompt_lens:
            key = compile_cache.aot_key(
                "generate/spec_admit", batch=plan.max_batch,
                total_len=bat.total_len, prompt_len=p_len, backend=backend,
                devices=ndev)
            if plan.aot and compile_cache.load_aot(key) is not None:
                stats["aot_hits"] += 1
                stats["edges"] += 1
                continue
            try:
                compiled = _spec_admit.lower(
                    bat._model, bat._draft_model, bat._params,
                    bat._draft_params, state_sds,
                    jax.ShapeDtypeStruct((), jnp.int32),
                    jax.ShapeDtypeStruct((1, int(p_len)), jnp.int32),
                    jax.ShapeDtypeStruct((2,), jnp.uint32),
                    bat._temperature, **bat._kw()).compile()
                stats["edges"] += 1
            except Exception:
                logger.warning("warmup: spec_admit(prompt_len=%d) lowering "
                               "failed", p_len, exc_info=True)
                continue
            if plan.aot and compile_cache.save_aot(key, compiled):
                stats["aot_serialized"] += 1
    stats["compile_ms"] = (time.perf_counter() - t0) * 1e3
    stats["cache_hits"] = compile_cache.hit_count() - hits0
    return stats


def warm_module_step(module: Any, batch: Any,
                     *, aot: bool = True) -> Optional[Dict[str, Any]]:
    """AOT-compile a built :class:`Module`'s train step against a
    representative ``batch`` (the ``engine/step`` edge).  Same
    load-AOT → lower().compile() → save-AOT ladder as
    :func:`warm_batcher`; returns stats or ``None`` when the module has
    no steps built."""
    steps = getattr(module, "_steps", None)
    state = getattr(module, "_state", None)
    if not steps or state is None:
        return None
    name = "window" if "window" in steps else "sync"
    step = steps[name]
    jitted = getattr(step, "jitted", step)
    args = (state, (batch,) * module._accum) if name == "window" \
        else (state, batch)
    stats = {"compile_ms": 0.0, "cache_hits": 0, "edges": 0,
             "aot_hits": 0, "aot_serialized": 0}
    hits0 = compile_cache.hit_count()
    t0 = time.perf_counter()
    shapes = "-".join(
        f"{tuple(x.shape)}{x.dtype}" for x in jax.tree_util.tree_leaves(batch)
        if hasattr(x, "shape"))
    key = compile_cache.aot_key(
        f"engine/step_{name}", shapes=shapes,
        backend=jax.default_backend(), devices=len(jax.devices()))
    from rocket_tpu.observe.ledger import get_goodput
    with get_goodput().timed("compile"):
        if aot and compile_cache.load_aot(key) is not None:
            stats["aot_hits"] += 1
            stats["edges"] += 1
        else:
            try:
                compiled = jitted.lower(*args).compile()
                stats["edges"] += 1
                if aot and compile_cache.save_aot(key, compiled):
                    stats["aot_serialized"] += 1
            except Exception:
                logger.warning("warmup: %s step lowering failed", name,
                               exc_info=True)
    stats["compile_ms"] = (time.perf_counter() - t0) * 1e3
    stats["cache_hits"] = compile_cache.hit_count() - hits0
    return stats
