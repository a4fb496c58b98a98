"""Pod-scale MPMD pipeline runner — one stage-local jitted program per
process, explicit activation send/recv over a transport, driven by a
per-stage 1F1B scheduler (arXiv 2412.14374).

The SPMD engine (:mod:`rocket_tpu.parallel.pipeline`) expresses every
schedule as one program on one controller: great on a single ICI domain,
but it caps the pod story — a single XLA program cannot span DCN, and the
single-controller 1F1B cannot start microbatch ``m``'s backward before
the caller's loss.  This module is the scaled form from the MPMD paper:

- **per-stage programs**: each stage (one process on a pod; one thread in
  the CPU-emulated tests) runs its own jitted chunk programs —
  ``pipeline/mpmd/chunk_fwd``, ``pipeline/mpmd/chunk_bwd``,
  ``pipeline/mpmd/loss_grad`` — registered at the
  :func:`~rocket_tpu.observe.ledger.ledger_call` chokepoint so the
  retrace sentinel covers them (the edges are shape-polymorphic across
  configs, so they are exempt from the zero-retrace assertion);
- **explicit transport**: boundary activations/cotangents move as tagged
  messages over a :class:`QueueTransport` (in-process, for tests) or a :class:`SocketEndpoint` (TCP loopback for the real
  2-process test; the same framing serves DCN between pod slices —
  ``multihost.stage_process_groups`` maps processes to stages);
- **per-stage 1F1B scheduler**: :func:`stage_schedule` emits each
  stage's work-item order.  The last stage computes the loss per
  microbatch and starts its backward immediately — the TRUE 1F1B
  residency bound (≤P live microbatches), measured here as
  ``max_live`` and asserted by the tests, not just derived;
- **goodput attribution**: every second a stage spends blocked on a recv
  lands in the goodput ledger as a ``pipeline/bubble/stage<p>`` bucket —
  bubble fraction becomes a measured, guardable number per stage
  (``tests/test_mpmd.py`` asserts interleaved(v=2) < gpipe on the same
  config).

Bit-equality contract: a run accumulates each chunk's parameter-gradient
contributions in ascending microbatch order and divides the loss/grad
sums by ``n_micro`` once at the end.  :func:`run_reference` replays the
SAME jitted chunk programs on one controller in that same order, so the
distributed run is bit-equal to the single-controller program — IEEE
addition is commutative but not associative, so the ORDER is the
contract, not a tolerance.
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from rocket_tpu.observe.ledger import (
    get_goodput,
    get_retrace_ledger,
    ledger_call,
)
from rocket_tpu.observe.trace import counter, span
from rocket_tpu.parallel.pipeline import (
    SCHEDULES,
    _chunk_apply,
    schedule_plan,
)
from rocket_tpu.utils.framing import FramedSocket

#: ``(kind, micro, chunk_slot)`` with kind in {"fwd", "bwd"}.
WorkItem = Tuple[str, int, int]

_RECV_TIMEOUT_S = 120.0


# ---------------------------------------------------------------------------
# per-stage scheduler
# ---------------------------------------------------------------------------


def stage_schedule(
    schedule: str,
    stage: int,
    n_stages: int,
    n_micro: int,
    n_chunks: int = 1,
) -> List[WorkItem]:
    """The ordered work items stage ``stage`` executes under ``schedule``.

    Correctness never depends on this order — every recv is tagged and
    blocks until its producer delivers — but the order IS the schedule:
    it decides when a stage sits in its ``pipeline/bubble`` bucket and
    how many forward residuals it holds (``max_live``).

    - ``gpipe``: all forwards (chunk-major, ascending micro), then all
      backwards (reverse chunk-major, ascending micro) — ``n_micro``
      residuals live at the peak.
    - ``1f1b``: ``P - 1 - stage`` warmup forwards, then strict
      fwd/bwd alternation, then the cooldown backwards — at most
      ``P - stage`` residuals live, the ≤P bound.
    - ``interleaved``: the chunked breadth-first walk (chunk slot
      ascending on the forward, descending on the backward): each item
      is ``1/v`` of a GPipe slab, so the fill/drain wait shrinks ~1/v.

    Every schedule issues each chunk's backwards in ascending microbatch
    order — the gradient-accumulation order bit-equality rests on.
    """
    if schedule not in SCHEDULES:
        raise ValueError(
            f"unknown schedule {schedule!r}; choose from {SCHEDULES}"
        )
    if schedule != "interleaved" and n_chunks != 1:
        raise ValueError(
            f"n_chunks={n_chunks} requires schedule='interleaved' "
            f"(got {schedule!r})"
        )
    if not 0 <= stage < n_stages:
        raise ValueError(f"stage {stage} out of range for {n_stages} stages")
    P, M, v = n_stages, n_micro, n_chunks
    if schedule == "1f1b":
        warm = min(P - 1 - stage, M)
        items: List[WorkItem] = [("fwd", m, 0) for m in range(warm)]
        done_bwd = 0
        for m in range(warm, M):
            items.append(("fwd", m, 0))
            items.append(("bwd", done_bwd, 0))
            done_bwd += 1
        items.extend(("bwd", m, 0) for m in range(done_bwd, M))
        return items
    fwd = [("fwd", m, c) for c in range(v) for m in range(M)]
    bwd = [("bwd", m, c) for c in reversed(range(v)) for m in range(M)]
    return fwd + bwd


# ---------------------------------------------------------------------------
# transports
# ---------------------------------------------------------------------------


class _TaggedReceiver:
    """Shared recv discipline: pull frames from ``_next()`` into a
    reorder buffer until the wanted ``(src, tag)`` appears; the time
    blocked is the caller's bubble."""

    def __init__(self) -> None:
        self._buf: Dict[Tuple[int, Any], Any] = {}

    def _next(self, src: int, timeout: float) -> Tuple[Any, Any]:
        raise NotImplementedError

    def recv(
        self, src: int, tag: Any, timeout: float = _RECV_TIMEOUT_S
    ) -> Tuple[Any, float]:
        """Blocking tagged receive; returns ``(value, seconds_waited)``."""
        key = (src, tag)
        t0 = time.perf_counter()
        deadline = t0 + timeout
        while key not in self._buf:
            remaining = deadline - time.perf_counter()
            if remaining <= 0:
                raise TimeoutError(
                    f"recv of {tag!r} from stage {src} timed out "
                    f"after {timeout:.0f}s"
                )
            got_tag, value = self._next(src, remaining)
            self._buf[(src, got_tag)] = value
        return self._buf.pop(key), time.perf_counter() - t0


class QueueTransport:
    """In-process transport: one FIFO per directed ``(src, dst)`` stage
    pair.  Sends never block (unbounded queues), so any
    dependency-consistent per-stage order is deadlock-free."""

    def __init__(self, n_stages: int) -> None:
        self.n_stages = n_stages
        self._queues = {
            (s, d): queue.Queue()
            for s in range(n_stages)
            for d in range(n_stages)
            if s != d
        }

    def endpoint(self, stage: int) -> "_QueueEndpoint":
        return _QueueEndpoint(self, stage)


class _QueueEndpoint(_TaggedReceiver):
    def __init__(self, hub: QueueTransport, stage: int) -> None:
        super().__init__()
        self._hub = hub
        self.stage = stage

    def send(self, dst: int, tag: Any, value: Any) -> None:
        self._hub._queues[(self.stage, dst)].put((tag, value))

    def _next(self, src: int, timeout: float) -> Tuple[Any, Any]:
        try:
            return self._hub._queues[(src, self.stage)].get(timeout=timeout)
        except queue.Empty:
            raise TimeoutError(
                f"no message from stage {src} within {timeout:.0f}s"
            )


class SocketEndpoint(_TaggedReceiver):
    """Point-to-point transport endpoint over one TCP socket —
    length-prefixed pickled ``(src, tag, ndarray)`` frames on the shared
    :class:`~rocket_tpu.utils.framing.FramedSocket` discipline (the same
    bytes the serving fleet's wire protocol rides).  The loopback form
    backs the real 2-process CPU test; the identical framing is what a
    DCN bridge between pod slices carries (one endpoint per neighbor
    edge, see ``multihost.stage_neighbors``)."""

    def __init__(self, sock: Any, stage: int) -> None:
        super().__init__()
        self._fs = sock if isinstance(sock, FramedSocket) \
            else FramedSocket(sock)
        self.stage = stage

    # -- connection setup ------------------------------------------------
    @classmethod
    def listen(
        cls, port: int, stage: int, host: str = "127.0.0.1",
        timeout: float = _RECV_TIMEOUT_S,
    ) -> "SocketEndpoint":
        return cls(FramedSocket.listen(port, host=host, timeout=timeout),
                   stage)

    @classmethod
    def connect(
        cls, host: str, port: int, stage: int,
        timeout: float = _RECV_TIMEOUT_S,
    ) -> "SocketEndpoint":
        return cls(FramedSocket.connect(host, port, timeout=timeout), stage)

    # -- framing ---------------------------------------------------------
    def send(self, dst: int, tag: Any, value: Any) -> None:
        self._fs.send_obj((self.stage, tag, np.asarray(value)))

    def _next(self, src: int, timeout: float) -> Tuple[Any, Any]:
        frame_src, tag, value = self._fs.recv_obj(timeout)
        if frame_src != src:
            raise ValueError(
                f"stage {self.stage} expected frames from {src}, "
                f"got one from {frame_src}"
            )
        return tag, jnp.asarray(value)

    def close(self) -> None:
        self._fs.close()


# ---------------------------------------------------------------------------
# stage-local jitted programs
# ---------------------------------------------------------------------------


class ChunkPrograms:
    """The three jit edges a stage dispatches — built once per runner,
    registered with the retrace ledger via :func:`ledger_call`.  The
    edges retrace across configs (chunk height / micro shape are part of
    the signature), so they are exempted from the zero-retrace sentinel
    rather than expected-compiled per shape."""

    FWD = "pipeline/mpmd/chunk_fwd"
    BWD = "pipeline/mpmd/chunk_bwd"
    LOSS = "pipeline/mpmd/loss_grad"

    def __init__(
        self,
        layer_fn: Callable[[Any, Any], Any],
        loss_fn: Optional[Callable[[Any], Any]] = None,
    ) -> None:
        get_retrace_ledger().exempt(self.FWD, self.BWD, self.LOSS)

        def fwd(chunk_params, x):
            return _chunk_apply(layer_fn, chunk_params, x)

        def bwd(chunk_params, x, dy):
            _, vjp = jax.vjp(fwd, chunk_params, x)
            return vjp(dy)  # (dparams, dx)

        self._fwd = jax.jit(fwd)
        self._bwd = jax.jit(bwd)
        self._loss_grad = None
        if loss_fn is not None:

            def loss_grad(chunk_params, x):
                def scalar(cp, xi):
                    return loss_fn(fwd(cp, xi))

                loss, grads = jax.value_and_grad(
                    scalar, argnums=(0, 1)
                )(chunk_params, x)
                return loss, grads[0], grads[1]

            self._loss_grad = jax.jit(loss_grad)

    def fwd(self, chunk_params: Any, x: Any) -> Any:
        return ledger_call(self._fwd, self.FWD, chunk_params, x)

    def bwd(self, chunk_params: Any, x: Any, dy: Any) -> Tuple[Any, Any]:
        return ledger_call(self._bwd, self.BWD, chunk_params, x, dy)

    def loss_grad(self, chunk_params: Any, x: Any) -> Tuple[Any, Any, Any]:
        if self._loss_grad is None:
            raise ValueError(
                "this stage owns the last chunk but was built without a "
                "loss_fn"
            )
        return ledger_call(self._loss_grad, self.LOSS, chunk_params, x)


# ---------------------------------------------------------------------------
# stage runner
# ---------------------------------------------------------------------------


@dataclass
class StageReport:
    """What one stage measured about its own run."""

    stage: int
    schedule: str
    n_items: int
    busy_s: float
    wait_s: float
    max_live: int  # peak in-flight forward residuals, in microbatches

    @property
    def bubble_fraction(self) -> float:
        total = self.busy_s + self.wait_s
        return self.wait_s / total if total > 0 else 0.0


def _tree_add(a: Any, b: Any) -> Any:
    return jax.tree_util.tree_map(jnp.add, a, b)


def _tree_div(a: Any, d: float) -> Any:
    return jax.tree_util.tree_map(lambda x: x / d, a)


def run_stage(
    stage: int,
    n_stages: int,
    programs: ChunkPrograms,
    chunk_params: Dict[int, Any],
    endpoint: Any,
    n_micro: int,
    schedule: str = "1f1b",
    n_chunks: int = 1,
    micros: Optional[Any] = None,
    goodput: bool = True,
) -> Tuple[Dict[int, Any], Optional[jax.Array], StageReport]:
    """Execute one stage's schedule to completion.

    ``chunk_params`` maps chunk slot ``c`` → this stage's params for
    global chunk ``k = c*n_stages + stage`` (leading dim = layers per
    chunk).  ``micros`` (``[n_micro, ...]``) is required on the stage
    owning chunk 0.  Returns ``(grads_by_slot, loss_or_None, report)`` —
    grads and loss are already divided by ``n_micro``; loss is only
    produced by the stage owning the last chunk.

    Residency contract: a forward stores ONE boundary input per in-flight
    microbatch; the backward recomputes the chunk under ``jax.vjp`` from
    that input and pops it.  ``report.max_live`` is the measured peak —
    ≤ ``n_stages - stage`` under 1F1B, ``n_micro`` under GPipe.
    """
    P, M, v = n_stages, n_micro, n_chunks
    last_chunk = v * P - 1
    items = stage_schedule(schedule, stage, P, M, v)
    gp = get_goodput() if goodput else None
    bucket = f"pipeline/bubble/stage{stage}"

    stash: Dict[Tuple[int, int], Any] = {}
    grads: Dict[int, Any] = {}
    loss_sum: Optional[jax.Array] = None
    busy = 0.0
    wait = 0.0
    max_live = 0

    with span("pipeline/mpmd/stage_run", stage=stage, schedule=schedule):
        for kind, m, c in items:
            k = c * P + stage
            if kind == "fwd":
                if k == 0:
                    x = jax.tree_util.tree_map(lambda a: a[m], micros)
                else:
                    x, dt = endpoint.recv((stage - 1) % P, ("a", k, m))
                    wait += dt
                    if gp is not None:
                        gp.add(bucket, dt)
                stash[(c, m)] = x
                max_live = max(max_live, len(stash))
                t0 = time.perf_counter()
                if k != last_chunk:
                    y = programs.fwd(chunk_params[c], x)
                    jax.block_until_ready(y)
                    busy += time.perf_counter() - t0
                    endpoint.send((stage + 1) % P, ("a", k + 1, m), y)
                else:
                    busy += time.perf_counter() - t0
            else:  # bwd
                x = stash.pop((c, m))
                if k == last_chunk:
                    t0 = time.perf_counter()
                    loss_m, dp, dx = programs.loss_grad(chunk_params[c], x)
                    jax.block_until_ready(dx)
                    busy += time.perf_counter() - t0
                    loss_sum = (
                        loss_m if loss_sum is None else loss_sum + loss_m
                    )
                else:
                    dy, dt = endpoint.recv((stage + 1) % P, ("g", k, m))
                    wait += dt
                    if gp is not None:
                        gp.add(bucket, dt)
                    t0 = time.perf_counter()
                    dp, dx = programs.bwd(chunk_params[c], x, dy)
                    jax.block_until_ready(dx)
                    busy += time.perf_counter() - t0
                # ascending-micro accumulation per chunk: the bit-equality
                # order contract with run_reference
                grads[c] = dp if c not in grads else _tree_add(grads[c], dp)
                if k != 0:
                    endpoint.send((stage - 1) % P, ("g", k - 1, m), dx)

    grads = {c: _tree_div(g, float(M)) for c, g in grads.items()}
    loss = None if loss_sum is None else loss_sum / float(M)
    counter("pipeline/mpmd/stage_wait_s", wait, stage=stage)
    counter("pipeline/mpmd/stage_busy_s", busy, stage=stage)
    return grads, loss, StageReport(
        stage=stage,
        schedule=schedule,
        n_items=len(items),
        busy_s=busy,
        wait_s=wait,
        max_live=max_live,
    )


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------


def split_chunks(
    stacked_params: Any, n_stages: int, n_chunks: int = 1
) -> List[Dict[int, Any]]:
    """Slice canonical layer-stacked params into each stage's chunk dict
    (stage ``p`` holds global chunks ``c*P + p``); the checkpoint layout
    stays canonical, exactly as the SPMD engine's interleave permutation."""
    leaves = jax.tree_util.tree_leaves(stacked_params)
    L = leaves[0].shape[0]
    if L % (n_stages * n_chunks) != 0:
        raise ValueError(
            f"layer dim {L} not divisible by n_stages*n_chunks = "
            f"{n_stages}*{n_chunks}; pick n_chunks so L % (P*n_chunks) == 0"
        )
    ell = L // (n_stages * n_chunks)

    def rows(k):
        return jax.tree_util.tree_map(
            lambda a: a[k * ell:(k + 1) * ell], stacked_params
        )

    return [
        {c: rows(c * n_stages + p) for c in range(n_chunks)}
        for p in range(n_stages)
    ]


def merge_chunk_grads(
    per_stage: List[Dict[int, Any]], n_stages: int, n_chunks: int
) -> Any:
    """Reassemble per-chunk grads back to the canonical stacked layout."""
    ordered = [
        per_stage[k % n_stages][k // n_stages]
        for k in range(n_stages * n_chunks)
    ]
    return jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, axis=0), *ordered
    )


@dataclass
class MpmdResult:
    loss: jax.Array
    grads: Any  # canonical stacked layout
    reports: List[StageReport]
    plan: dict  # schedule_plan() analytic accounting

    @property
    def bubble_fraction(self) -> float:
        """Measured fleet bubble: total recv-wait over total stage time."""
        waits = sum(r.wait_s for r in self.reports)
        busy = sum(r.busy_s for r in self.reports)
        return waits / (waits + busy) if waits + busy > 0 else 0.0


def run_pipeline(
    layer_fn: Callable[[Any, Any], Any],
    stacked_params: Any,
    micros: Any,
    loss_fn: Callable[[Any], Any],
    n_stages: int,
    schedule: str = "1f1b",
    n_chunks: int = 1,
    transport: Optional[QueueTransport] = None,
    goodput: bool = True,
) -> MpmdResult:
    """CPU-emulated MPMD run: every stage in its own thread, activations
    over a :class:`QueueTransport` — the in-process twin of the
    one-process-per-stage pod deployment (same scheduler, same programs,
    same transport discipline; only the endpoint class differs)."""
    leaves = jax.tree_util.tree_flatten(micros)[0]
    M = leaves[0].shape[0]
    transport = transport if transport is not None else QueueTransport(n_stages)
    stage_params = split_chunks(stacked_params, n_stages, n_chunks)
    programs = ChunkPrograms(layer_fn, loss_fn)

    results: List[Optional[Tuple[Dict[int, Any], Any, StageReport]]] = (
        [None] * n_stages
    )
    errors: List[BaseException] = []

    def worker(p: int) -> None:
        try:
            results[p] = run_stage(
                p, n_stages, programs, stage_params[p],
                transport.endpoint(p), M,
                schedule=schedule, n_chunks=n_chunks,
                micros=micros if p == 0 else None,
                goodput=goodput,
            )
        except BaseException as exc:  # noqa: BLE001 — surfaced below
            errors.append(exc)

    threads = [
        threading.Thread(target=worker, args=(p,), daemon=True)
        for p in range(n_stages)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=_RECV_TIMEOUT_S + 30)
    if errors:
        raise errors[0]
    if any(r is None for r in results):
        raise TimeoutError("MPMD stage thread did not finish")

    grads = merge_chunk_grads([r[0] for r in results], n_stages, n_chunks)
    loss = results[-1][1]
    reports = [r[2] for r in results]
    return MpmdResult(
        loss=loss,
        grads=grads,
        reports=reports,
        plan=schedule_plan(schedule, n_stages, M, n_chunks),
    )


def run_lockstep(
    layer_fn: Callable[[Any, Any], Any],
    stacked_params: Any,
    micros: Any,
    loss_fn: Callable[[Any], Any],
    n_stages: int,
    schedule: str = "gpipe",
    n_chunks: int = 1,
    goodput: bool = True,
) -> MpmdResult:
    """Lockstep CPU-proxy run: the bubble-measurement driver.

    On a machine with fewer cores than stages (every CPU CI host), the
    free-running threaded driver measures OS-scheduler noise, not the
    schedule.  This driver runs all stages on one thread in global tick
    rounds — the SPMD tick discipline, executed: each round every stage
    attempts its NEXT work item, executing it (real jitted compute, real
    measured seconds) only when the tagged input message has actually
    arrived, else logging one idle round.  Sends land in the mailbox at
    the END of the round, so a hop costs one round, exactly like the
    ``ppermute`` rotation.

    A stage's wait seconds are ``idle_rounds × mean measured item
    seconds`` — structural idleness priced at that stage's own measured
    compute rate — and are routed to the goodput ledger's
    ``pipeline/bubble/stage<p>`` bucket, which is what
    ``tests/test_mpmd.py`` compares across schedules.  Loss/grads follow the same order
    contract as the other drivers (bit-equal to :func:`run_reference`).
    """
    leaves = jax.tree_util.tree_flatten(micros)[0]
    M = leaves[0].shape[0]
    P, v = n_stages, n_chunks
    last_chunk = v * P - 1
    stage_params = split_chunks(stacked_params, P, v)
    programs = ChunkPrograms(layer_fn, loss_fn)
    items = [stage_schedule(schedule, p, P, M, v) for p in range(P)]
    cursors = [0] * P
    mailbox: Dict[Tuple[int, Any], Any] = {}
    stash: List[Dict[Tuple[int, int], Any]] = [{} for _ in range(P)]
    grads: List[Dict[int, Any]] = [{} for _ in range(P)]
    busy = [0.0] * P
    idle_rounds = [0] * P
    done_items = [0] * P
    max_live = [0] * P
    loss_sum: Optional[jax.Array] = None

    with span("pipeline/mpmd/lockstep_run", schedule=schedule,
              n_stages=P, n_chunks=v):
        while any(cursors[p] < len(items[p]) for p in range(P)):
            pending: List[Tuple[int, Any, Any]] = []
            progressed = False
            for p in range(P):
                if cursors[p] >= len(items[p]):
                    continue
                kind, m, c = items[p][cursors[p]]
                k = c * P + p
                if kind == "fwd":
                    if k == 0:
                        x = jax.tree_util.tree_map(lambda a: a[m], micros)
                    else:
                        key = (p, ("a", k, m))
                        if key not in mailbox:
                            idle_rounds[p] += 1
                            continue
                        x = mailbox.pop(key)
                    stash[p][(c, m)] = x
                    max_live[p] = max(max_live[p], len(stash[p]))
                    if k != last_chunk:
                        t0 = time.perf_counter()
                        y = programs.fwd(stage_params[p][c], x)
                        jax.block_until_ready(y)
                        busy[p] += time.perf_counter() - t0
                        pending.append(((p + 1) % P, ("a", k + 1, m), y))
                else:
                    if k == last_chunk:
                        x = stash[p].pop((c, m))
                        t0 = time.perf_counter()
                        loss_m, dp, dx = programs.loss_grad(
                            stage_params[p][c], x
                        )
                        jax.block_until_ready(dx)
                        busy[p] += time.perf_counter() - t0
                        loss_sum = (
                            loss_m if loss_sum is None else loss_sum + loss_m
                        )
                    else:
                        key = (p, ("g", k, m))
                        if key not in mailbox:
                            idle_rounds[p] += 1
                            continue
                        dy = mailbox.pop(key)
                        x = stash[p].pop((c, m))
                        t0 = time.perf_counter()
                        dp, dx = programs.bwd(stage_params[p][c], x, dy)
                        jax.block_until_ready(dx)
                        busy[p] += time.perf_counter() - t0
                    grads[p][c] = (
                        dp if c not in grads[p]
                        else _tree_add(grads[p][c], dp)
                    )
                    if k != 0:
                        pending.append(((p - 1) % P, ("g", k - 1, m), dx))
                cursors[p] += 1
                done_items[p] += 1
                progressed = True
            for dst, tag, val in pending:
                mailbox[(dst, tag)] = val
            if not progressed and not pending:
                stuck = {
                    p: items[p][cursors[p]]
                    for p in range(P) if cursors[p] < len(items[p])
                }
                raise RuntimeError(
                    f"lockstep schedule deadlocked; blocked heads: {stuck}"
                )

    gp = get_goodput() if goodput else None
    reports = []
    for p in range(P):
        mean_item = busy[p] / done_items[p] if done_items[p] else 0.0
        wait_s = idle_rounds[p] * mean_item
        if gp is not None:
            gp.add(f"pipeline/bubble/stage{p}", wait_s)
        counter("pipeline/mpmd/idle_rounds", idle_rounds[p], stage=p)
        reports.append(StageReport(
            stage=p, schedule=schedule, n_items=done_items[p],
            busy_s=busy[p], wait_s=wait_s, max_live=max_live[p],
        ))
    merged = merge_chunk_grads(
        [{c: _tree_div(g, float(M)) for c, g in grads[p].items()}
         for p in range(P)],
        P, v,
    )
    return MpmdResult(
        loss=loss_sum / float(M),
        grads=merged,
        reports=reports,
        plan=schedule_plan(schedule, P, M, v),
    )


def run_reference(
    layer_fn: Callable[[Any, Any], Any],
    stacked_params: Any,
    micros: Any,
    loss_fn: Callable[[Any], Any],
    n_stages: int = 1,
    n_chunks: int = 1,
) -> Tuple[jax.Array, Any]:
    """The single-controller oracle: the SAME jitted chunk programs, run
    sequentially per microbatch in ascending order — the order every MPMD
    schedule's per-chunk accumulation follows, so the distributed run is
    bit-equal by construction, not by tolerance."""
    leaves = jax.tree_util.tree_flatten(micros)[0]
    M = leaves[0].shape[0]
    stage_params = split_chunks(stacked_params, n_stages, n_chunks)
    programs = ChunkPrograms(layer_fn, loss_fn)
    n_total = n_stages * n_chunks
    chunks = [stage_params[k % n_stages][k // n_stages] for k in range(n_total)]

    grads: List[Any] = [None] * n_total
    loss_sum = None
    for m in range(M):
        x = jax.tree_util.tree_map(lambda a: a[m], micros)
        inputs = []
        for k in range(n_total - 1):
            inputs.append(x)
            x = programs.fwd(chunks[k], x)
        inputs.append(x)
        loss_m, dp, dx = programs.loss_grad(chunks[n_total - 1], inputs[-1])
        loss_sum = loss_m if loss_sum is None else loss_sum + loss_m
        grads[n_total - 1] = (
            dp if grads[n_total - 1] is None
            else _tree_add(grads[n_total - 1], dp)
        )
        for k in range(n_total - 2, -1, -1):
            dp, dx = programs.bwd(chunks[k], inputs[k], dx)
            grads[k] = dp if grads[k] is None else _tree_add(grads[k], dp)
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, axis=0),
        *[_tree_div(g, float(M)) for g in grads],
    )
    return loss_sum / float(M), stacked
