"""rocket_tpu — a TPU-native, event-driven training-pipeline framework.

Capability-equivalent to dsenushkin/rocket (see SURVEY.md): a composable tree
of lifecycle-driven capsules over an Attributes blackboard — but with the
execution engine built on JAX/XLA: jitted train steps under a
jax.sharding.Mesh, XLA collectives over ICI, bf16 policy, Orbax persistence.

The public surface is flattened here the same way the reference flattens
``rocket.core`` into ``rocket.*`` (``rocket/__init__.py:1``).
"""

import time as _time

_IMPORT_T0 = _time.perf_counter_ns()  # start-up record: startup/import

from rocket_tpu.core import (
    Attributes,
    Capsule,
    Dispatcher,
    Events,
    Loss,
    Module,
    Optimizer,
    Scheduler,
)
from rocket_tpu.data import (
    ArraySource,
    DataLoader,
    Dataset,
    ConcatSource,
    GeneratorSource,
    MapSource,
    IterableSource,
    TokenFileSource,
)
from rocket_tpu.engine.sentinel import DivergenceSentinel
from rocket_tpu.launch import Launcher, Looper, notebook_launch
from rocket_tpu.observe import (
    Accuracy,
    ClassStats,
    ImageLogger,
    Meter,
    Metric,
    Perplexity,
    Profiler,
    StatMetric,
    Throughput,
    Tracker,
)
from rocket_tpu.persist import Checkpointer
from rocket_tpu.runtime import Runtime

__version__ = "0.1.0"

__all__ = [
    "ArraySource",
    "Attributes",
    "Capsule",
    "Checkpointer",
    "DataLoader",
    "Dataset",
    "Dispatcher",
    "DivergenceSentinel",
    "Events",
    "ConcatSource",
    "GeneratorSource",
    "MapSource",
    "IterableSource",
    "Launcher",
    "Looper",
    "Loss",
    "notebook_launch",
    "Accuracy",
    "ClassStats",
    "ImageLogger",
    "Meter",
    "Metric",
    "Perplexity",
    "Profiler",
    "StatMetric",
    "Throughput",
    "TokenFileSource",
    "Module",
    "Optimizer",
    "Runtime",
    "Scheduler",
    "Tracker",
    "__version__",
]

from rocket_tpu.observe.trace import get_startup as _get_startup  # noqa: E402

_get_startup().mark("startup/import", _IMPORT_T0, _time.perf_counter_ns(),
                    package=__name__)
