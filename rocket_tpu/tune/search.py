"""Cost-model-seeded successive-halving search over bench.py probes.

Shape of a run (:func:`successive_halving`):

1. enumerate the :class:`~rocket_tpu.tune.space.TuneSpace`, score every
   point with the analytical roofline (:mod:`.cost_model`), keep the
   ``seed_k`` best-predicted — the Placeto-style "learned prior seeds
   the measured search" step, collapsed to the analytical model we
   already trust for MFU accounting;
2. successive halving: measure all survivors with a SHORT timed probe,
   keep the best ``1/eta`` fraction, repeat with a longer probe — cheap
   rungs kill obviously-bad points, the budget concentrates on
   contenders;
3. persist the winner as a tune record (:mod:`.store`).

Every probe is a FRESH subprocess running ``bench.bench_gpt2`` with the
fully-merged point (explicit ``tune=`` — immune to env overrides and to
previously-saved records), under a timeout: a miscompile, OOM, or hang
costs one rung slot, never the run.  A chip belongs to one process at a
time, so the searching parent never initialises a JAX backend: the
device kind and backend it needs come from one short identity child
(:func:`device_identity`) that has exited before the first probe starts.
:func:`autotune` adds the zero re-search contract: an existing matching
record short-circuits the whole search (``probes == 0``) unless
``force=True``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Any, Callable, Dict, List, Optional, Sequence

from rocket_tpu.tune.cost_model import predict_point
from rocket_tpu.tune.space import TuneSpace, gpt2_space
from rocket_tpu.tune.store import best_tune, canonical_tune_key, save_tune

_PROBE_MARK = "TUNE_PROBE_RESULT "


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def bench_probe(tune: Dict[str, Any], steps: int, warmup: int,
                timeout_s: float = 600.0) -> Dict[str, Any]:
    """One subprocess-isolated timed probe through ``bench.bench_gpt2``.

    Returns the bench record (``value`` tokens/s, ``mfu``, ...) or
    ``{"value": None, "error": ...}`` — a dead point, never an
    exception.  The child gets the COMPLETE point as an explicit
    ``tune=`` argument, which outranks both ``BENCH_GPT2_TUNE`` and the
    tune store inside ``bench_gpt2``, so a probe measures exactly its
    point regardless of ambient state.
    """
    child = (
        "import sys, json\n"
        f"sys.path.insert(0, {_repo_root()!r})\n"
        "import bench\n"
        f"rec = bench.bench_gpt2({int(steps)}, {int(warmup)}, "
        f"tune=json.loads({json.dumps(json.dumps(tune))}))\n"
        f"print({_PROBE_MARK!r} + json.dumps(rec))\n"
    )
    env = dict(os.environ)
    if "XLA_FLAGS" in env:
        # A forced host-platform device count (the test harness sets 8)
        # would make the child's mesh reject probe batches not divisible
        # by it; probes measure the DEFAULT single-process topology.
        kept = [f for f in env["XLA_FLAGS"].split()
                if not f.startswith("--xla_force_host_platform_device_count")]
        env["XLA_FLAGS"] = " ".join(kept)
    try:
        proc = subprocess.run(
            [sys.executable, "-c", child], env=env,
            timeout=timeout_s, capture_output=True, text=True,
        )
    except subprocess.TimeoutExpired:
        return {"value": None,
                "error": f"probe timed out after {timeout_s}s"}
    for line in reversed((proc.stdout or "").splitlines()):
        if line.startswith(_PROBE_MARK):
            try:
                return json.loads(line[len(_PROBE_MARK):])
            except ValueError:
                break
    tail = (proc.stderr or "").strip().splitlines()
    return {"value": None,
            "error": tail[-1] if tail else f"exit {proc.returncode}"}


def device_identity() -> Dict[str, str]:
    """``{"device": device_kind, "backend": ...}`` of this machine, asked
    of a short child process so the caller holds no chip while its probe
    children need it."""
    child = (
        "import json, jax\n"
        "print(json.dumps({'device': jax.devices()[0].device_kind,\n"
        "                  'backend': jax.default_backend()}))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", child],
        timeout=300.0, capture_output=True, text=True,
    )
    if proc.returncode != 0:
        tail = (proc.stderr or "").strip().splitlines()
        raise RuntimeError(
            f"device identity child failed (exit {proc.returncode}): "
            f"{tail[-1] if tail else 'no output'}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def successive_halving(
    space: Optional[TuneSpace] = None,
    *,
    model: str = "gpt2",
    base: Optional[Dict[str, Any]] = None,
    seed_k: int = 9,
    eta: int = 3,
    rung_steps: Sequence[int] = (3, 8, 20),
    warmup: int = 1,
    probe: Optional[Callable[..., Dict[str, Any]]] = None,
    probe_timeout_s: float = 600.0,
    save: bool = True,
    log: Callable[[str], None] = print,
    identity: Optional[Dict[str, str]] = None,
    device_kind: Optional[str] = None,
) -> Dict[str, Any]:
    """Run the search; returns (and by default persists) the tune record.

    ``base`` pins tune keys across every candidate (e.g. a fixed batch,
    or the tiny CPU-proxy model dims).  ``rung_steps`` are the timed
    steps per rung — each rung keeps ``ceil(n / eta)`` survivors by
    measured ``value``; failed probes are dropped before ranking.
    ``identity`` is the machine the record is stamped with (default: ask
    :func:`device_identity`); ``device_kind`` is the chip whose published
    peaks seed the ranking (default: the identity's) — a CPU-proxy search
    has no peaks of its own and names the chip it models.
    """
    from rocket_tpu.observe.trace import get_tracer

    space = space if space is not None else gpt2_space()
    base = dict(base or {})
    probe = probe if probe is not None else bench_probe
    tracer = get_tracer()
    identity = identity if identity is not None else device_identity()
    device_kind = device_kind or identity["device"]

    # -- cost-model seeding -------------------------------------------
    seen: set = set()
    scored: List[tuple] = []
    for point in space.candidates():
        merged = dict(base, **point)
        key = canonical_tune_key(space.bench_tune(merged))
        if key in seen:  # distinct fragments, same executable
            continue
        seen.add(key)
        pred = predict_point(merged, device_kind)
        scored.append((pred["seconds"], merged, pred))
    scored.sort(key=lambda item: item[0])
    survivors = [
        {"tune": t, "predicted": p} for _, t, p in scored[:max(1, seed_k)]
    ]
    log(f"tune: space of {space.size} -> {len(scored)} distinct points, "
        f"cost model seeds top {len(survivors)}")

    # -- successive halving over measured probes ----------------------
    probes = 0
    rungs: List[Dict[str, Any]] = []
    for rung, steps in enumerate(rung_steps):
        measured = []
        for cand in survivors:
            with tracer.span("tune/probe", rung=rung,
                             key=canonical_tune_key(cand["tune"])):
                rec = probe(space.bench_tune(cand["tune"]), steps, warmup,
                            probe_timeout_s)
            probes += 1
            cand = dict(cand, measured=rec)
            if rec.get("value"):
                measured.append(cand)
            else:
                tracer.counter("tune/probe/dead", 1, rung=rung)
                log(f"tune: rung {rung} dropped point "
                    f"({rec.get('error')})")
        if not measured:
            raise RuntimeError(
                f"tune search: every probe in rung {rung} failed — "
                f"nothing to rank (see probe errors above)"
            )
        measured.sort(key=lambda c: -c["measured"]["value"])
        keep = max(1, -(-len(measured) // eta))  # ceil
        if rung == len(rung_steps) - 1:
            keep = 1
        rungs.append({
            "rung": rung, "steps": steps,
            "candidates": [
                {"tune": c["tune"], "value": c["measured"]["value"],
                 "mfu": c["measured"].get("mfu")} for c in measured
            ],
        })
        survivors = measured[:keep]
        log(f"tune: rung {rung} ({steps} steps) measured "
            f"{len(measured)}, kept {keep}; best "
            f"{survivors[0]['measured']['value']} tok/s")

    winner = survivors[0]
    record = {
        "model": model,
        **identity,
        "batch": winner["tune"].get("batch"),
        "tune": winner["tune"],
        "value": winner["measured"]["value"],
        "mfu": winner["measured"].get("mfu"),
        "predicted": winner.get("predicted"),
        "probes": probes,
        "rungs": rungs,
    }
    if save:
        path = save_tune(record)
        log(f"tune: saved winner to {path}")
    return record


def autotune(
    model: str = "gpt2",
    space: Optional[TuneSpace] = None,
    *,
    base: Optional[Dict[str, Any]] = None,
    force: bool = False,
    identity: Optional[Dict[str, str]] = None,
    **search_kw: Any,
) -> Dict[str, Any]:
    """Search only when no matching record exists.

    An existing record for (model, local device, local backend) returns
    immediately with ``record["probes"] == 0`` and ``"reused": True`` —
    the zero re-search contract the smoke test pins.  ``force=True``
    always searches.
    """
    identity = identity if identity is not None else device_identity()
    if not force:
        hit = best_tune(model=model, device=identity["device"],
                        backend=identity["backend"])
        if hit is not None:
            return dict(hit, probes=0, reused=True)
    return successive_halving(space, model=model, base=base,
                              identity=identity, **search_kw)
