"""Serving worker — the subprocess half of a process-backed replica.

``python -m rocket_tpu.serve.worker --connect HOST:PORT --replica-id ID``
connects back to the supervisor that spawned it (the supervisor binds an
ephemeral port FIRST, so the rendezvous never races), receives a
:class:`~rocket_tpu.serve.wire.WorkerSpec`, builds its ServingLoop from
the spec's dotted builder reference — restoring weights through the
elastic-restore gate when the spec names a snapshot root — and then
answers the one-in-flight RPC stream: ``SUBMIT`` offers a request
(side-effect-free refusal, the router owns the typed result), ``STEP``
runs one serving round and ships every typed result produced so far,
``PING`` answers liveness, ``SHUTDOWN`` exits cleanly.

Death model: this process holds NO salvage responsibility.  The
supervisor's :class:`~rocket_tpu.serve.procfleet.ProcReplica` shadows
every accepted request; results this worker produced but never shipped
die with it, which is exactly what keeps the exactly-once contract — an
unshipped result was never observed, so the salvaged request's re-route
emits the single one.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
import traceback
from typing import Any, Optional

from rocket_tpu.serve import wire
from rocket_tpu.utils.framing import FramedSocket, parse_address

_HELLO_TIMEOUT_S = 120.0
# Idle RPC wait: the supervisor drives a beat at least every probe
# interval; a socket quiet for this long means the supervisor is gone
# and the worker should die with it rather than leak.
_IDLE_TIMEOUT_S = 600.0


def _locate_params(manifest: Any) -> tuple:
    """Find the params subtree inside a snapshot's manifest: the item
    key and the path prefix under it.  A serving snapshot stores a bare
    ``{"params": ...}`` item (prefix ``()``); a TRAINER snapshot — the
    emergency tier flushes whatever the run's capsules hold — stores the
    whole TrainState under the module's checkpoint key with leaf paths
    like ``state/params/...``.  Falls back to the bare layout when the
    manifest is absent or unrecognized."""
    items = (manifest or {}).get("items") or {}
    if not items or "params" in items:
        return "params", ()
    for key, meta in items.items():
        for rec in meta.get("structure", []) or []:
            parts = str(rec.get("path", "")).split("/")
            if "params" in parts:
                idx = parts.index("params")
                return key, tuple(parts[: idx + 1])
    return "params", ()


def restore_params(restore_dir: str, targets: Any) -> Any:
    """Elastic-restore a ``params`` tree from the newest valid snapshot
    under ``restore_dir`` onto whatever devices THIS process got.

    Tier election matches ``resume("auto")``: :func:`~rocket_tpu.persist.
    integrity.latest_valid` scans the ``DEFAULT_SUBDIRS`` — weights AND
    the emergency tier — so a worker spawned right after a preemption
    restores the newest state, even when the only committed snapshot is
    the SIGTERM-window emergency flush.  That flush may hold a trainer
    capsule layout (params nested inside a TrainState); the manifest's
    recorded leaf paths locate the subtree, and the restore goes through
    ``restore_item(partial=True)`` to pull just the params.

    The PR 13 gate runs first: :func:`~rocket_tpu.persist.integrity.
    check_reshard` validates every target leaf (shape, mesh-axis names,
    spec rank) against the snapshot's mesh-stamped manifest, so a worker
    spawned onto an incompatible topology fails loudly with the remedy
    instead of serving mis-placed weights."""
    from rocket_tpu.persist import integrity
    from rocket_tpu.persist.orbax_io import CheckpointIO
    from rocket_tpu.persist.publish import PUBLISH_SUBDIR

    # Workers ALSO elect the publish tier (train-while-serve): a worker
    # respawned mid-run must come back on the newest published weights,
    # not the weights from before the run started.  The trainer's own
    # resume deliberately ignores this subdir — a params-only
    # publication cannot resume optimizer state.
    subdirs = tuple(integrity.DEFAULT_SUBDIRS) + (PUBLISH_SUBDIR,)
    path = integrity.latest_valid(restore_dir, subdirs=subdirs,
                                  do_quarantine=False)
    if path is None:
        path = integrity.resolve_restore_path(restore_dir,
                                              do_quarantine=False)
    if path is None:
        raise FileNotFoundError(
            f"no valid snapshot under {restore_dir!r} to restore from")
    manifest = integrity.read_manifest(path)
    item_key, prefix = _locate_params(manifest)
    nested: Any = targets
    for part in reversed(prefix):
        nested = {part: nested}
    if manifest is not None:
        integrity.check_reshard(manifest, {item_key: nested})
    io = CheckpointIO(use_async=False)
    try:
        out = io.restore_item(path, item_key, target=nested,
                              partial=bool(prefix))
    finally:
        io.close()
    for part in prefix:
        out = out[part]
    return out


def serve(fs: FramedSocket, loop: Any, *,
          clock=time.monotonic, on_shutdown=None) -> int:
    """Answer the supervisor's RPC stream until SHUTDOWN or socket loss.

    Every request gets exactly one reply frame; an exception escaping a
    handler answers ``ERROR`` (the supervisor declares this replica dead
    and salvages from its shadow)."""
    kvstore = getattr(loop, "kvstore", None)
    while True:
        try:
            kind, payload = wire.recv_msg(fs, _IDLE_TIMEOUT_S)
        except (ConnectionError, OSError, TimeoutError):
            return 1    # supervisor gone — die with it
        try:
            if kind == wire.SUBMIT:
                req = wire.unpack_request(payload, clock=clock)
                handoff = getattr(req, "_handoff", None)
                if handoff is not None:
                    rej = loop.submit_prefilled(req, handoff,
                                                record_rejection=False)
                else:
                    rej = loop.submit(req, record_rejection=False)
                wire.send_msg(fs, wire.REPLY, {
                    "accepted": rej is None, "load": int(loop.load)})
            elif kind == wire.STEP:
                ran = bool(loop.run_round())
                reply = {
                    "results": loop.drain_results(),
                    "busy": ran or int(loop.load) > 0,
                    "load": int(loop.load),
                    "health": loop.health.value,
                    "latency": loop.latency,
                    "slo_latency": getattr(loop, "slo_latency", None),
                    "counters": loop.counters.snapshot(),
                    # v3: this clock stamp + the supervisor's send/recv
                    # stamps feed the per-connection OffsetEstimator, so
                    # offset drift is re-measured every round, not just
                    # at PING cadence.
                    "mono_ns": time.perf_counter_ns(),
                }
                if kvstore is not None:
                    reply["kv_hashes"] = kvstore.drain_new_hashes()
                    # the anti-delta: evicted hashes, so the supervisor's
                    # SharedPrefixIndex forgets this replica's dead claims
                    reply["kv_evicted"] = kvstore.drain_evicted_hashes()
                wire.send_msg(fs, wire.REPLY, reply)
            elif kind == wire.PING:
                wire.send_msg(fs, wire.PONG, {
                    "load": int(loop.load),
                    "health": loop.health.value,
                    "pid": os.getpid(),
                    "mono_ns": time.perf_counter_ns(),
                })
            elif kind == wire.DRAIN:
                loop.drain()
                wire.send_msg(fs, wire.REPLY, {"health": loop.health.value})
            elif kind == wire.RENAME:
                # a promoted standby adopts the scale-up replica's id:
                # every result from here on is stamped with the new
                # identity, so the router's shadow stays coherent.
                loop.replica_id = payload
                loop.queue.name = payload
                wire.send_msg(fs, wire.REPLY, {"replica_id": payload})
            elif kind == wire.NEW_WEIGHTS:
                # Hot-swap happens HERE — between decode rounds by
                # construction: STEP RPCs are the only way rounds run,
                # and the supervisor's one-in-flight discipline means
                # this frame can never overlap one.
                from rocket_tpu.observe import trace as _tr
                ctx = _tr.TraceContext.from_wire(payload.get("ctx"))
                if ctx is not None and ctx.sampled:
                    _tr.instant("serve/new_weights",
                                trace_id=ctx.trace_id,
                                version=payload.get("version"))
                ok = loop.swap_weights(
                    payload["path"], payload.get("version"),
                    deep_verify=bool(payload.get("deep_verify", True)))
                wire.send_msg(fs, wire.REPLY, {
                    "swapped": bool(ok),
                    "version": int(getattr(loop, "weights_version", -1)),
                    "counters": loop.counters.snapshot(),
                })
            elif kind == wire.ROLLBACK_WEIGHTS:
                ok = loop.rollback_weights()
                wire.send_msg(fs, wire.REPLY, {
                    "swapped": bool(ok),
                    "version": int(getattr(loop, "weights_version", -1)),
                    "counters": loop.counters.snapshot(),
                })
            elif kind == wire.COLLECT:
                from rocket_tpu.observe.ledger import (get_goodput,
                                                       get_retrace_ledger)
                from rocket_tpu.tune import compile_cache as _cc
                wire.send_msg(fs, wire.REPLY, {
                    "counters": loop.counters.snapshot(),
                    "latency": loop.latency,
                    "slo_latency": getattr(loop, "slo_latency", None),
                    "ledger": get_retrace_ledger().snapshot(),
                    "goodput": get_goodput().snapshot(),
                    "compile_cache": _cc.snapshot(),
                })
            elif kind == wire.SHUTDOWN:
                if on_shutdown is not None:
                    # flush side outputs (the tracer's ring dump) BEFORE
                    # the BYE ships: the supervisor reaps — SIGKILL —
                    # the moment it reads the reply, so anything written
                    # after is a lost race
                    try:
                        on_shutdown()
                    except Exception:
                        pass
                wire.send_msg(fs, wire.BYE, {"results": loop.drain_results()})
                try:
                    loop.close()
                except Exception:
                    pass
                return 0
            else:
                wire.send_msg(fs, wire.ERROR, f"unknown message {kind!r}")
        except (ConnectionError, OSError):
            return 1
        except Exception as exc:
            try:
                wire.send_msg(fs, wire.ERROR, repr(exc))
            except Exception:
                return 1


def main(argv: Optional[list] = None) -> int:
    parser = argparse.ArgumentParser(
        description="rocket_tpu serving worker (spawned by ProcReplica)")
    parser.add_argument("--connect", required=True, metavar="HOST:PORT",
                        help="supervisor rendezvous address")
    parser.add_argument("--replica-id", default=None,
                        help="fleet identity stamped on every result")
    args = parser.parse_args(argv)

    host, port = parse_address(args.connect)
    fs = FramedSocket.connect(host, port)
    try:
        kind, payload = wire.recv_msg(fs, _HELLO_TIMEOUT_S)
        if kind != wire.HELLO:
            wire.send_msg(fs, wire.ERROR,
                          f"expected HELLO, got {kind!r}")
            return 2
        try:
            spec = wire.check_hello(payload)
        except (wire.ProtocolMismatch, ValueError) as exc:
            # The typed refusal travels back as the ERROR payload, so
            # the supervisor's spawn failure names the remedy.
            wire.send_msg(fs, wire.ERROR, str(exc))
            return 2
        # Warm-start tier (ISSUE 15): arm the persistent compile cache
        # and the ledgers BEFORE the build, so every compile the build
        # and the WarmupPlan pay is (a) served from / written to the
        # per-host disk cache and (b) timed into the goodput ``compile``
        # bucket this worker reports in READY.
        from rocket_tpu.observe.ledger import arm_ledgers, get_goodput
        from rocket_tpu.tune import compile_cache

        arm_ledgers()
        t_build = time.perf_counter()
        try:
            cache_armed = compile_cache.enable_compile_cache()
            loop = spec.build()
            if args.replica_id is not None:
                loop.replica_id = args.replica_id
                loop.queue.name = args.replica_id
            # Fleet page tier: the spec carries the pool's address; the
            # client attaches post-build (accelerant — a dead pool means
            # cold prefills, not a dead worker).  Skip when the builder
            # already attached a client or the loop has no kvstore.
            if getattr(spec, "kvpool", None) \
                    and getattr(loop, "kvstore", None) is not None \
                    and getattr(loop, "kvpool", None) is None:
                try:
                    from rocket_tpu.serve.kvpool import KVPoolClient
                    loop.kvpool = KVPoolClient.connect(spec.kvpool)
                except Exception:
                    pass
        except Exception:
            wire.send_msg(fs, wire.ERROR, traceback.format_exc())
            return 2
        build_ms = (time.perf_counter() - t_build) * 1e3
        # Distributed tracing: with ROCKET_TPU_TRACE_DIR set (the
        # supervisor exports it before spawning), arm this process's
        # tracer, label the ring with the worker's fleet identity, and
        # dump it into the shared directory at orderly exit — the
        # timeline assembler stitches those dumps against the
        # supervisor's ring using the per-connection clock offsets.
        trace_dir = os.environ.get("ROCKET_TPU_TRACE_DIR")
        tracer = None
        if trace_dir:
            from rocket_tpu.observe import trace as _trace

            tracer = _trace.arm()
            tracer.set_anchor()
            tracer.meta.update({
                "role": "worker",
                "replica": args.replica_id or "worker",
                "pid": os.getpid(),
            })
        import jax

        wire.send_msg(fs, wire.READY, {
            "proto": wire.PROTOCOL_VERSION,
            "pid": os.getpid(),
            "devices": int(jax.local_device_count()),
            "platform": jax.default_backend(),
            "build_ms": build_ms,
            "compile_ms": get_goodput().snapshot().get("compile_s", 0.0)
            * 1e3,
            "cache_hits": compile_cache.hit_count(),
            "cache_dir": cache_armed,
            "warm_stats": dict(getattr(loop, "warm_stats", None) or {}),
        })
        dump = None
        if tracer is not None:
            def dump() -> None:
                name = (f"worker-{args.replica_id or 'worker'}-"
                        f"{os.getpid()}.json")
                tracer.dump_json(os.path.join(trace_dir, name))
        rc = serve(fs, loop, on_shutdown=dump)
        if tracer is not None and rc != 0:
            try:
                # socket-loss exits (supervisor gone) never saw SHUTDOWN:
                # dump here.  An orderly exit (rc 0) dumped before its BYE,
                # and the supervisor reaps this process with SIGKILL as
                # soon as it reads that BYE: writing the file again would
                # race the kill and could leave it cut short.
                dump()
            except Exception:
                pass  # a failed dump must not turn a clean exit dirty
        return rc
    finally:
        fs.close()


if __name__ == "__main__":
    sys.exit(main())
