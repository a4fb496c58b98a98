"""Serving robustness layer over the continuous-batching decoder.

See :mod:`rocket_tpu.serve.loop` for the architecture and the
fault-free bit-equality contract; ``docs/reliability.md`` ("Serving
reliability") for the operator view.
"""

import time as _time

_IMPORT_T0 = _time.perf_counter_ns()  # start-up record: startup/import

from rocket_tpu.serve.autoscale import (
    Autoscaler,
    AutoscaleCounters,
    SLOPolicy,
    register_fleet_source,
    successive_halving_capacity,
)
from rocket_tpu.serve.feed import WeightFeed, register_swap_source
from rocket_tpu.serve.fleet import PrefillReplica, Replica
from rocket_tpu.serve.kvpool import (
    KVPagePool,
    KVPoolClient,
    register_kvpool_source,
)
from rocket_tpu.serve.kvstore import (
    PrefixKVStore,
    PrefixMatch,
    SharedPrefixIndex,
    page_hashes,
    register_kvstore_source,
)
from rocket_tpu.serve.loadgen import (
    ReplayReport,
    TenantSpec,
    TraceConfig,
    TraceEvent,
    replay_trace,
    synth_trace,
)
from rocket_tpu.serve.loop import ServingLoop
from rocket_tpu.serve.metrics import (
    DEFAULT_SLO_TARGETS,
    ClassLatency,
    FleetCounters,
    ServeCounters,
    ServeLatency,
    register_slo_source,
)
from rocket_tpu.serve.policy import (
    DEFAULT_LADDER,
    DegradationLevel,
    DegradationPolicy,
)
from rocket_tpu.serve.procfleet import (
    ProcReplica,
    collect_offsets,
    write_offsets,
)
from rocket_tpu.serve.queue import DEFAULT_CLASS_WEIGHTS, AdmissionQueue
from rocket_tpu.serve.router import FleetRouter
from rocket_tpu.serve.types import (
    SLO_CLASSES,
    Completed,
    DeadlineExceeded,
    Failed,
    HealthState,
    Overloaded,
    PreemptTicket,
    ReplicaId,
    Request,
    Result,
)
from rocket_tpu.serve.watchdog import DispatchWatchdog
from rocket_tpu.serve.wire import WorkerSpec

__all__ = [
    "AdmissionQueue",
    "Autoscaler",
    "AutoscaleCounters",
    "ClassLatency",
    "Completed",
    "DEFAULT_CLASS_WEIGHTS",
    "DEFAULT_LADDER",
    "DEFAULT_SLO_TARGETS",
    "DeadlineExceeded",
    "DegradationLevel",
    "DegradationPolicy",
    "DispatchWatchdog",
    "Failed",
    "FleetCounters",
    "FleetRouter",
    "HealthState",
    "KVPagePool",
    "KVPoolClient",
    "Overloaded",
    "PreemptTicket",
    "PrefillReplica",
    "PrefixKVStore",
    "PrefixMatch",
    "ProcReplica",
    "Replica",
    "ReplayReport",
    "ReplicaId",
    "Request",
    "Result",
    "SLO_CLASSES",
    "SLOPolicy",
    "ServeCounters",
    "ServeLatency",
    "ServingLoop",
    "SharedPrefixIndex",
    "TenantSpec",
    "TraceConfig",
    "TraceEvent",
    "WeightFeed",
    "WorkerSpec",
    "page_hashes",
    "register_fleet_source",
    "register_kvpool_source",
    "register_kvstore_source",
    "register_slo_source",
    "register_swap_source",
    "replay_trace",
    "collect_offsets",
    "synth_trace",
    "write_offsets",
]

from rocket_tpu.observe.trace import get_startup as _get_startup  # noqa: E402

_get_startup().mark("startup/import", _IMPORT_T0, _time.perf_counter_ns(),
                    package=__name__)
