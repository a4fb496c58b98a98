"""Typed request/result vocabulary for the serving robustness layer.

Every request submitted to :class:`~rocket_tpu.serve.ServingLoop` is
accounted for by EXACTLY ONE typed result — robustness must not become
silence, and it must not become an untyped exception either:

- :class:`Completed` — the request finished (possibly truncated by a
  degradation cap, possibly served by the beam lane);
- :class:`Overloaded` — admission control rejected it (bounded queue
  full, or the loop is draining).  The caller sees the rejection
  IMMEDIATELY at submit time instead of the queue growing without bound;
- :class:`DeadlineExceeded` — the deadline passed.  ``stage='queue'``
  means the entry was shed BEFORE prefill (it could not possibly have
  met its deadline); ``stage='decode'`` means the row was evicted at the
  next round boundary, and ``tokens`` carries the partial output;
- :class:`Failed` — a watchdog trip (or a step exception) killed the
  in-flight row; ``tokens`` carries the last good host-side partial.

Deadlines are ABSOLUTE timestamps on the loop's injected clock
(``time.monotonic`` by default), so tests can drive eviction with a fake
clock while the device work stays real.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Any, Dict, Optional

import numpy as np

# Identity of a serving replica in a fleet — rides every Result's
# ``meta["replica"]`` so routing decisions are assertable without
# reaching into router internals.
ReplicaId = str

# SLO classes, in strict priority order (multi-tenant serving).  The
# order is load-bearing: the weighted-fair queue breaks ties toward the
# earlier class, the serving loop preempts ``batch`` rows to make room
# for the earlier classes, and the degradation ladder is fed only the
# non-batch backlog — batch pressure sheds/preempts batch, it never
# degrades interactive quality.
SLO_CLASSES = ("interactive", "standard", "batch")


class HealthState(enum.Enum):
    """Readiness of the serving loop — the state machine the demo (and a
    real load balancer) watches: ``SERVING`` = full quality, ``DEGRADED``
    = the ladder is engaged or a watchdog trip is still being recovered
    from, ``DRAINING`` = no new admissions, in-flight/queued requests
    finish."""

    SERVING = "serving"
    DEGRADED = "degraded"
    DRAINING = "draining"


@dataclasses.dataclass
class Request:
    """One generation request.

    ``prompt`` is a 1-D int32 token array; ``deadline`` is an absolute
    clock value (``None`` = no deadline); ``max_new_tokens`` caps the
    output below the batcher's buffer room (``None`` = fill the buffer);
    ``beam=True`` asks for the beam lane (honored at degradation level 0
    when the loop has a ``beam_fn``; demoted to the greedy continuous
    lane otherwise — the result records the demotion).  ``session`` is an
    opaque affinity key: the fleet router keeps turns of one session on
    the replica whose prefix-cache store holds their KV pages (falling
    back to least-loaded, and dropping the stamp when that replica is
    healed).

    ``tenant`` names who submitted the request (an opaque accounting
    key); ``slo_class`` is one of :data:`SLO_CLASSES` and decides how
    the request competes for capacity: weighted-fair admission,
    per-class budgets and shed accounting, and — for ``batch`` — cheap
    round-boundary preemption (evict-to-kvstore, resume later,
    bit-equal).  Both cross the RPC wire.

    ``due_at`` is the caller's own stamp of when the request was due
    (a load generator's schedule, on the caller's clock); the loop never
    reads it and copies it through untouched onto the typed result, so
    latency from when the request SHOULD have arrived — not from when a
    late generator got round to submitting it — can be computed from the
    result alone.

    Distributed tracing stamps a private
    :class:`~rocket_tpu.observe.trace.TraceContext` as ``_ctx`` at
    submit (same convention as the other lifecycle stamps ``_submit_ts``
    / ``_enq_ts`` / ``_handoff``); it rides the v3 wire frames so every
    process a request visits tags its events with the same trace_id.
    """

    rid: Any
    prompt: np.ndarray
    deadline: Optional[float] = None
    max_new_tokens: Optional[int] = None
    beam: bool = False
    session: Optional[Any] = None
    tenant: Optional[str] = None
    slo_class: str = "standard"
    due_at: Optional[float] = None

    def __post_init__(self) -> None:
        if self.slo_class not in SLO_CLASSES:
            raise ValueError(
                f"request {self.rid!r}: slo_class must be one of "
                f"{SLO_CLASSES}, got {self.slo_class!r}"
            )
        prompt = np.asarray(self.prompt, np.int32)
        if prompt.ndim == 2 and prompt.shape[0] == 1:
            prompt = prompt[0]
        if prompt.ndim != 1 or prompt.shape[0] < 1:
            raise ValueError(
                f"request {self.rid!r}: prompt must be a non-empty 1-D "
                f"token array, got shape {np.asarray(self.prompt).shape}"
            )
        if self.max_new_tokens is not None and self.max_new_tokens < 1:
            raise ValueError(
                f"request {self.rid!r}: max_new_tokens must be >= 1, got "
                f"{self.max_new_tokens}"
            )
        self.prompt = prompt


@dataclasses.dataclass(frozen=True)
class Result:
    """Base of the typed result family: which request, and when (on the
    loop's clock) its fate was decided.

    ``meta`` records WHERE the fate was decided: the serving replica's
    :data:`ReplicaId` and its degradation level at completion
    (``{"replica": ..., "level": ...}``).  A fleet-level rejection (no
    replica ever owned the request) carries ``replica=None``."""

    rid: Any
    finished_at: float
    meta: Optional[Dict[str, Any]] = None


@dataclasses.dataclass(frozen=True)
class Completed(Result):
    """``tokens`` is the fixed-length ``[total_len]`` buffer row
    (eos-tail-filled, same contract as the one-dispatch path); ``n_tok``
    the number of real (prompt + generated) tokens.  ``truncated`` marks
    a degradation-cap cutoff; ``via_beam``/``beam_demoted`` record how a
    beam request was actually served."""

    tokens: np.ndarray = None
    n_tok: int = 0
    via_beam: bool = False
    beam_demoted: bool = False
    truncated: bool = False
    # the row's instants on the loop's clock (None where the request
    # never got that far) and the request's own due_at, untouched:
    # queue wait = admitted_at - submitted_at (or - due_at), TTFT =
    # first_token_at - submitted_at, TPOT = (finished_at -
    # first_token_at) / (generated tokens - 1)
    submitted_at: Optional[float] = None
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    due_at: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class Overloaded(Result):
    reason: str = "queue full"


@dataclasses.dataclass(frozen=True)
class DeadlineExceeded(Result):
    tokens: Optional[np.ndarray] = None
    n_tok: int = 0
    stage: str = "queue"  # 'queue' = shed before prefill; 'decode' = evicted
    # the row's instants on the loop's clock (None where the request
    # never got that far) and the request's own due_at, untouched:
    # queue wait = admitted_at - submitted_at (or - due_at), TTFT =
    # first_token_at - submitted_at, TPOT = (finished_at -
    # first_token_at) / (generated tokens - 1)
    submitted_at: Optional[float] = None
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    due_at: Optional[float] = None


@dataclasses.dataclass
class PreemptTicket:
    """A preempted batch-class row, parked for later resumption.

    NOT a result — the preempted request still owes its caller exactly
    one typed result, which the RESUMED run emits.  ``tokens`` is the
    full token prefix decoded so far (prompt + generated, 1-D int32):
    the resume admission replays it as the prompt, importing whatever
    prefix pages the preemption exported into the kvstore, so the
    continuation is bit-equal to an uninterrupted run at the cost of
    (at most) the un-paged tail's prefill.  ``produced`` counts
    generated tokens relative to the ORIGINAL prompt — the resume's
    remaining ``max_new_tokens`` budget subtracts it."""

    req: "Request"
    tokens: np.ndarray
    produced: int
    preempted_at: float


@dataclasses.dataclass(frozen=True)
class Failed(Result):
    """``dump_path`` points at the flight-recorder dump written when the
    failure was detected (``None`` when no recorder was armed) — the
    caller's ticket attaches the exact host-side timeline of the trip."""

    tokens: Optional[np.ndarray] = None
    n_tok: int = 0
    reason: str = "step failure"
    dump_path: Optional[str] = None
    # the row's instants on the loop's clock (None where the request
    # never got that far) and the request's own due_at, untouched:
    # queue wait = admitted_at - submitted_at (or - due_at), TTFT =
    # first_token_at - submitted_at, TPOT = (finished_at -
    # first_token_at) / (generated tokens - 1)
    submitted_at: Optional[float] = None
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    due_at: Optional[float] = None
