"""Wire protocol between a fleet supervisor and a serving worker process.

One message is one length-prefixed frame (:mod:`rocket_tpu.utils.framing`
— the same bytes as the MPMD pipeline transport) holding a pickled
``(kind, payload)`` tuple.  Everything that crosses is host data: typed
results carry numpy token buffers, and a :class:`~rocket_tpu.models.
generate.KVHandoff` travels via :meth:`~KVHandoff.to_host` — its stated
wire format — so neither side ever pickles a device array.

The RPC discipline is strictly one-in-flight request/reply, supervisor
side initiating: the supervisor sends ``SUBMIT``/``STEP``/``PING``/...,
the worker answers with exactly one reply frame (``ERROR`` on an escaped
exception).  That keeps the worker single-threaded and makes "the socket
went quiet" an unambiguous death signal for the supervisor's probe.

Deadlines cross as REMAINING seconds: ``Request.deadline`` is absolute
on the submitting clock, which a different process does not share —
:func:`pack_request` subtracts the local clock, :func:`unpack_request`
re-anchors on the worker's, so a salvaged request re-routed to another
process keeps exactly its remaining budget.
"""

from __future__ import annotations

import dataclasses
import importlib
import time
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from rocket_tpu.observe.trace import TraceContext
from rocket_tpu.serve.types import Request
from rocket_tpu.utils.framing import FramedSocket

# -- protocol version --------------------------------------------------------

# Bumped whenever a frame's pickled layout changes incompatibly.  The
# version crosses in BOTH handshake directions — the HELLO payload and
# the READY reply each carry ``proto`` — so a supervisor and a worker
# from different builds reject each other with a typed
# :class:`ProtocolMismatch` naming the remedy, instead of un-pickling
# garbage three RPCs into the run.
#   1: versioned handshake; NEW_WEIGHTS / ROLLBACK_WEIGHTS swap RPCs.
#   2: multi-tenant serving — Request.tenant / Request.slo_class ride
#      the SUBMIT frame (a v1 peer would silently drop the class and
#      serve batch floods at interactive priority, so this is a
#      compatibility break, not an additive field).
#   3: distributed request tracing — a TraceContext 3-tuple rides
#      SUBMIT / FETCH_PAGES / NEW_WEIGHTS payloads ("ctx") and STEP /
#      PONG replies carry the worker's perf_counter_ns ("mono_ns") for
#      per-connection clock-offset estimation.  Both are read with
#      tolerant .get() — a v2 frame unpacks with ctx=None, unsampled —
#      so the bump documents intent; degradation is graceful.
PROTOCOL_VERSION = 3


class ProtocolMismatch(RuntimeError):
    """Supervisor and worker speak different wire-protocol versions."""

    def __init__(self, ours: int, theirs: Any, side: str) -> None:
        super().__init__(
            f"wire protocol mismatch: this {side} speaks version {ours}, "
            f"peer announced {theirs!r}. Remedy: supervisor and worker "
            f"must run the same rocket_tpu build — update the worker "
            f"environment (or the supervisor's) so both import the same "
            f"rocket_tpu.serve.wire.PROTOCOL_VERSION, then respawn."
        )
        self.ours = int(ours)
        self.theirs = theirs
        self.side = side


# -- message kinds -----------------------------------------------------------

HELLO = "hello"          # supervisor -> worker: {"proto", "spec"}
READY = "ready"          # worker -> supervisor: loop built, serving
SUBMIT = "submit"        # packed request -> {"accepted": bool, "load": int}
STEP = "step"            # run one round -> results/busy/load/health/...
PING = "ping"            # liveness probe -> PONG with load/health
PONG = "pong"
DRAIN = "drain"          # stop admitting; in-flight work finishes
COLLECT = "collect"      # counters + latency snapshot (no round)
SHUTDOWN = "shutdown"    # orderly exit -> BYE, then the process exits
BYE = "bye"
RENAME = "rename"        # re-stamp the worker's fleet identity (a warm
                         # standby promoted into the router must emit
                         # results under the adopting replica's id)
REPLY = "reply"          # generic success reply
ERROR = "error"          # worker -> supervisor: payload is the repr

# Train-while-serve (serve/feed.py).  NEW_WEIGHTS announces a committed
# publication ({"path", "version"}); the worker verifies + hot-swaps
# BETWEEN decode rounds (the one-in-flight RPC discipline makes that
# structural: a swap RPC can never overlap a STEP round) and replies
# with the outcome.  ROLLBACK_WEIGHTS re-swaps onto the previously
# applied published version (bounded rollback after divergence).
NEW_WEIGHTS = "new_weights"
ROLLBACK_WEIGHTS = "rollback_weights"

# Fleet KV page tier (serve/kvpool.py).  These cross between a replica's
# KVPoolClient and the supervisor-hosted KVPagePool, NOT on the
# supervisor<->worker RPC socket — the pool runs its own listener so a
# mid-decode page fetch never contends with the one-in-flight STEP RPC.
FETCH_PAGES = "fetch_pages"  # client -> pool: {"hashes": [bytes, ...]}
PUSH_PAGES = "push_pages"    # client -> pool: binary page-chain blob
PAGES = "pages"              # pool -> client: binary page-chain blob
PAGE_NACK = "page_nack"      # pool -> client: no usable prefix (stale hint)


def send_msg(fs: FramedSocket, kind: str, payload: Any = None) -> None:
    fs.send_obj((kind, payload))


def recv_msg(fs: FramedSocket, timeout: float) -> Tuple[str, Any]:
    msg = fs.recv_obj(timeout)
    if not (isinstance(msg, tuple) and len(msg) == 2):
        raise ValueError(f"malformed wire message: {type(msg)!r}")
    return msg


# -- worker spec -------------------------------------------------------------


@dataclasses.dataclass
class WorkerSpec:
    """Everything a worker process needs to build its ServingLoop.

    ``builder`` is a DOTTED reference (``"module.path:function"``) to a
    module-level callable returning a ServingLoop — a reference, not a
    pickled closure, so the spec crosses to a fresh interpreter that
    imports and calls it (seeded jax init being deterministic, two
    processes building the same spec hold bit-identical weights).
    ``kwargs`` must be plain picklable data.  ``restore_dir`` arms the
    elastic-restore path: the builder restores params from the newest
    valid snapshot under it (validated by ``check_reshard`` against
    whatever devices this worker got) instead of seeding them.
    ``kvpool`` is the supervisor-hosted page pool's ``"host:port"``
    address; when set (and the built loop has a kvstore), the worker
    attaches a :class:`~rocket_tpu.serve.kvpool.KVPoolClient` so
    admit-misses consult the fleet tier before cold prefill.
    """

    builder: str
    kwargs: Optional[Dict[str, Any]] = None
    restore_dir: Optional[str] = None
    kvpool: Optional[str] = None

    def resolve(self) -> Callable[..., Any]:
        mod_name, sep, attr = self.builder.partition(":")
        if not sep:
            raise ValueError(
                f"builder must be 'module:function', got {self.builder!r}")
        fn = getattr(importlib.import_module(mod_name), attr, None)
        if not callable(fn):
            raise ValueError(f"builder {self.builder!r} is not callable")
        return fn

    def build(self) -> Any:
        kwargs = dict(self.kwargs or {})
        if self.restore_dir is not None:
            kwargs["restore_dir"] = self.restore_dir
        return self.resolve()(**kwargs)


# -- handshake ---------------------------------------------------------------


def hello_payload(spec: "WorkerSpec") -> Dict[str, Any]:
    """The HELLO frame's payload: the WorkerSpec wrapped with this
    build's protocol version."""
    return {"proto": PROTOCOL_VERSION, "spec": spec}


def check_hello(payload: Any) -> "WorkerSpec":
    """Worker-side HELLO validation: returns the spec, or raises a typed
    :class:`ProtocolMismatch` when the supervisor announced a different
    version (a bare WorkerSpec — the pre-versioning frame — counts as
    version 0)."""
    if isinstance(payload, WorkerSpec):
        raise ProtocolMismatch(PROTOCOL_VERSION, 0, side="worker")
    if not isinstance(payload, dict):
        raise ProtocolMismatch(PROTOCOL_VERSION, None, side="worker")
    proto = payload.get("proto")
    if proto != PROTOCOL_VERSION:
        raise ProtocolMismatch(PROTOCOL_VERSION, proto, side="worker")
    spec = payload.get("spec")
    if not isinstance(spec, WorkerSpec):
        raise ValueError(
            f"HELLO payload carries no WorkerSpec (got {type(spec)!r})")
    return spec


def check_ready(payload: Any) -> Dict[str, Any]:
    """Supervisor-side READY validation: returns the payload dict, or
    raises :class:`ProtocolMismatch` when the worker announced a
    different version (a READY without ``proto`` counts as version 0)."""
    info = dict(payload or {})
    proto = info.get("proto", 0)
    if proto != PROTOCOL_VERSION:
        raise ProtocolMismatch(PROTOCOL_VERSION, proto, side="supervisor")
    return info


# -- request / result packing ------------------------------------------------


def pack_request(req: Request, *,
                 clock: Callable[[], float] = time.monotonic
                 ) -> Dict[str, Any]:
    """Request -> plain wire dict (deadline as remaining seconds, any
    prefilled handoff as host numpy)."""
    out: Dict[str, Any] = {
        "rid": req.rid,
        "prompt": np.asarray(req.prompt, np.int32),
        "remaining": None if req.deadline is None
        else float(req.deadline) - clock(),
        "max_new_tokens": req.max_new_tokens,
        "beam": bool(req.beam),
        "session": req.session,
        "tenant": req.tenant,
        "slo_class": req.slo_class,
        "due_at": req.due_at,
    }
    handoff = getattr(req, "_handoff", None)
    if handoff is not None:
        out["handoff"] = handoff.to_host()
    ctx = getattr(req, "_ctx", None)
    if ctx is not None:
        out["ctx"] = ctx.to_wire()
    return out


def unpack_request(wire: Dict[str, Any], *,
                   clock: Callable[[], float] = time.monotonic) -> Request:
    req = Request(
        rid=wire["rid"],
        prompt=wire["prompt"],
        deadline=None if wire.get("remaining") is None
        else clock() + float(wire["remaining"]),
        max_new_tokens=wire.get("max_new_tokens"),
        beam=bool(wire.get("beam", False)),
        session=wire.get("session"),
        tenant=wire.get("tenant"),
        slo_class=wire.get("slo_class", "standard"),
        due_at=wire.get("due_at"),
    )
    handoff = wire.get("handoff")
    if handoff is not None:
        req._handoff = handoff
    ctx = TraceContext.from_wire(wire.get("ctx"))
    if ctx is not None:
        # crossing the wire makes this a CHILD hop: a non-empty parent
        # tells the worker-side serve loop to emit a flow continuation
        # ("t"), never a second flow start for the same request
        req._ctx = ctx.child(ctx.parent or "wire")
    return req
