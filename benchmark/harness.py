"""The harness: driven by data, knows no cell and no architecture.

``BENCHMARK.json`` names a cell's configuration and traffic mix; this module
finds ``configs/<config>.json`` and ``traffic/<mix>.json`` by those names,
loads ``archs/<reference>.py`` (the configuration file's ``reference``: the
sizes, the program's module, the leaves, the plain reference and the counts
of that family of models), hands the cell to ``kinds/<kind>.py`` (the
traffic file's ``kind``), and turns what comes back into the result line.
Per-layer metrics are found the same way: ``metrics/<metric>.json`` names a
module under ``readers/``.

A later PR adds files and entries; it edits nothing here.
"""

from __future__ import annotations

import dataclasses
import gc
import hashlib
import importlib
import importlib.util
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PROCESS_START = time.perf_counter()


def log(message: str) -> None:
    """A progress line on standard error with the seconds since the process
    started; set-up is most of a run, so each stage says when it ended."""
    held = ""
    if "jax" in sys.modules:
        import jax

        stats = jax.devices()[0].memory_stats() or {}
        if stats:
            held = (f" [device holds {stats.get('bytes_in_use', 0) / 2**30:.2f}"
                    f" GiB, peak {stats.get('peak_bytes_in_use', 0) / 2**30:.2f}]")
    print(f"[bench +{time.perf_counter() - PROCESS_START:7.1f}s] "
          f"{message}{held}", file=sys.stderr, flush=True)


class BenchmarkError(RuntimeError):
    """The benchmark cannot run as asked (no chip, unknown device kind,
    a name that resolves to no file).  No result line is printed."""


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with its files resolved.  ``family`` is
    the module ``archs/<reference>.py`` of the configuration's architecture
    and ``arch`` its ``normalise(config)``: the sizes in the benchmark's own
    keys (``vocab``, ``layers`` ...)."""

    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    arch: Dict[str, Any]
    family: Any
    manifest: Dict[str, Any]
    bench_dir: str

    @property
    def kind(self) -> str:
        return self.traffic["kind"]

    def end_to_end(self) -> List[Dict]:
        return [m for m in self.manifest["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> List[Dict]:
        reported = {m["name"] for m in self.end_to_end()}
        return [m for m in self.manifest["per_layer"]
                if m["moves"] in reported
                and self.name in m.get("workloads", [self.name])]


def load_json(path: str) -> Dict:
    with open(path) as fh:
        return json.load(fh)


def load_manifest(path: Optional[str] = None) -> Dict:
    return load_json(path or os.path.join(ROOT, "BENCHMARK.json"))


def resolve_cell(name: str, manifest: Optional[Dict] = None,
                 bench_dir: str = HERE, root: str = ROOT) -> Cell:
    """Find the cell and its files by name; a name with no file is an
    error, never a default."""
    manifest = manifest or load_manifest()
    entries = {w["name"]: w for w in manifest["workloads"]}
    if name not in entries:
        raise BenchmarkError(
            f"no workload {name!r}; BENCHMARK.json has {sorted(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    if entry["config"] not in configs:
        raise BenchmarkError(f"workload {name!r} names config "
                             f"{entry['config']!r}, which configs lacks")
    config_path = os.path.join(root, configs[entry["config"]]["file"])
    traffic_path = os.path.join(bench_dir, "traffic",
                                entry["traffic"] + ".json")
    for path in (config_path, traffic_path):
        if not os.path.isfile(path):
            raise BenchmarkError(f"workload {name!r}: no file {path}")
    config = load_json(config_path)
    family = load_family(config, bench_dir, config_path)
    return Cell(name=name, chips=int(entry["chips"]),
                config_name=entry["config"], traffic_name=entry["traffic"],
                config=config, traffic=load_json(traffic_path),
                arch=family.normalise(config), family=family,
                manifest=manifest, bench_dir=bench_dir)

FAMILY_NAMES = ("normalise", "draft", "program", "leaf_shapes", "leaf_name",
                "reference", "counts")


def load_family(config: Dict, bench_dir: str = HERE,
                config_path: str = "the configuration file"):
    """``archs/<reference>.py`` of a configuration file: everything that
    depends on the architecture.  A file without the key, a name with no
    module, or a module without one of the seven names is an error, never
    a default."""
    name = config.get("reference")
    if not isinstance(name, str) or not name:
        raise BenchmarkError(
            f"{config_path} names no architecture: it needs a 'reference' "
            f"key, the name of a module under archs/")
    try:
        family = _load_module(bench_dir, "archs", name)
    except ModuleNotFoundError as exc:
        if exc.name != f"benchmark.archs.{name}":
            raise                   # the module is there; an import of its is not
        raise BenchmarkError(
            f"{config_path}: 'reference' is {name!r}, and there is no "
            f"archs/{name}.py") from exc
    lacking = [n for n in FAMILY_NAMES if not hasattr(family, n)]
    if lacking:
        raise BenchmarkError(
            f"archs/{name}.py lacks {lacking} of the architecture's names")
    return family


# -- the device ---------------------------------------------------------------


def peaks_for(device_kind: str) -> Dict:
    """Published peaks of a device kind, from the one table the benchmark
    keeps; a kind that is not in it is an error, never a default."""
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table or device_kind == "source":
        raise BenchmarkError(
            f"device kind {device_kind!r} is not in peaks.json "
            f"({sorted(k for k in table if k != 'source')}); a peak is never "
            f"assumed")
    return table[device_kind]


def require_chips(cell: Cell) -> Dict:
    """Refuse anything but a TPU with the chips the cell asks for, and a
    device kind with no published peak."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchmarkError(
            f"needs a TPU; JAX found platform {devices[0].platform!r} "
            f"({devices[0].device_kind})")
    if len(devices) < cell.chips:
        raise BenchmarkError(
            f"workload {cell.name!r} needs {cell.chips} chip(s); JAX found "
            f"{len(devices)}")
    peaks_for(devices[0].device_kind)
    return device_record(cell.chips)


def device_record(chips: int) -> Dict:
    import jax

    devices = jax.devices()[:chips]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def arm_compile_cache() -> str:
    """JAX's persistent cache where ``JAX_COMPILATION_CACHE_DIR`` says, else
    at the fixed ``experiments/compile_cache/`` of this checkout (the
    program's own default, so the program and the benchmark agree)."""
    import jax

    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, "experiments", "compile_cache")
    os.makedirs(directory, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return directory


class CompileCounter:
    """Counts compile requests (a cache hit is still a new program inside
    the window) between :meth:`open` and :meth:`close`."""

    _EVENTS = ("/jax/compilation_cache/compile_requests_use_cache",)
    _DURATIONS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self) -> None:
        self.total = 0
        self.in_window = 0
        self._open = False
        from jax._src import monitoring

        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _bump(self) -> None:
        self.total += 1
        if self._open:
            self.in_window += 1

    def _on_event(self, event: str, **kw: Any) -> None:
        if event in self._EVENTS:
            self._bump()

    def _on_duration(self, event: str, duration: float, **kw: Any) -> None:
        # counted only where the cache is off and no request event fires
        if event in self._DURATIONS and not self.total:
            self._bump()

    def open(self) -> None:
        self._open = True

    def close(self) -> None:
        self._open = False


# -- tracing ------------------------------------------------------------------


class DeviceTrace:
    """``jax.profiler`` around a stretch of the window; the Python tracer is
    off (it slows the host), TraceMe host spans stay on."""

    def __init__(self, directory: str) -> None:
        self.directory = directory
        self.t0_ns = self.t1_ns = None

    def start(self) -> None:
        import shutil

        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench/anchor"):
            self.anchor_perf_ns = time.perf_counter_ns()
        self.t0_ns = time.perf_counter_ns()

    def stop(self) -> None:
        import jax

        self.t1_ns = time.perf_counter_ns()
        jax.profiler.stop_trace()

    @property
    def active(self) -> bool:
        return self.t0_ns is not None and self.t1_ns is None

    def reduce(self, chips: int):
        import glob
        import shutil

        from benchmark import trace_reduce

        files = glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb"))
        if not files:
            raise BenchmarkError("the profiler wrote no .xplane.pb")
        events = trace_reduce.load_xplane(files[0])
        shutil.rmtree(self.directory, ignore_errors=True)
        reduced = trace_reduce.Reduced(events, chips=chips)
        anchor = reduced.anchor_ns()
        if anchor is not None:
            # put the window's ends, stamped on perf_counter_ns, on the
            # trace's clock; the program's spans move by the same offset
            self.offset_ns = anchor - self.anchor_perf_ns
            reduced.window_ns = (self.t0_ns + self.offset_ns,
                                 self.t1_ns + self.offset_ns)
        return reduced

    def on_trace_clock(self, spans):
        """``(name, start_perf_ns, end_perf_ns)`` -> the trace's clock."""
        off = getattr(self, "offset_ns", None)
        if off is None:
            return []
        return [(n, s + off, t + off) for n, s, t in spans]


def work_dir(name: str) -> str:
    """A directory for what a run leaves behind, inside the checkout and
    listed in ``.gitignore``."""
    path = os.path.join(ROOT, ".benchwork", name)
    os.makedirs(path, exist_ok=True)
    return path


# -- the result line ----------------------------------------------------------


def percentile(values: List[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0-100) by linear interpolation; None for no
    samples."""
    if not values:
        return None
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def read_per_layer(cell: Cell, ctx: Dict) -> Dict[str, Dict]:
    """Each per-layer metric of this cell through its own reader.  A reader
    that finds nothing to read returns None and the metric is left out."""
    out: Dict[str, Dict] = {}
    for metric in cell.per_layer():
        path = os.path.join(cell.bench_dir, "metrics",
                            metric["name"] + ".json")
        if not os.path.isfile(path):
            raise BenchmarkError(f"per-layer metric {metric['name']!r} has "
                                 f"no file {path}")
        spec = load_json(path)
        reader = _load_module(cell.bench_dir, "readers", spec["reader"])
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def _load_module(bench_dir: str, package: str, name: str):
    """``<bench_dir>/<package>/<name>.py`` as a module (an architecture, a
    kind, a reader): from the benchmark's own package where the directory
    is the benchmark's, else by path (the tests' toy benchmarks bring files
    of their own) and, failing that, the benchmark's of that name."""
    if os.path.abspath(bench_dir) == HERE:
        return importlib.import_module(f"benchmark.{package}.{name}")
    path = os.path.abspath(os.path.join(bench_dir, package, name + ".py"))
    if not os.path.isfile(path):
        return importlib.import_module(f"benchmark.{package}.{name}")
    # one module a file and a process, as an import gives: an architecture's
    # flax module is a static argument of the program's jitted functions.
    # The alias holds the whole path, so two directories that each bring an
    # archs/x.py keep a module each; a file rewritten in place loads anew.
    alias = "_bench_%s_%s_%s" % (
        package, name, hashlib.sha1(path.encode()).hexdigest()[:12])
    stat = os.stat(path)
    stamp = (stat.st_mtime_ns, stat.st_size)
    module = sys.modules.get(alias)
    if module is not None and module.__dict__.get("_bench_stamp") == stamp:
        return module
    spec = importlib.util.spec_from_file_location(alias, path)
    module = importlib.util.module_from_spec(spec)
    module._bench_stamp = stamp
    sys.modules[alias] = module      # dataclasses look their module up
    try:
        spec.loader.exec_module(module)
    except BaseException:
        del sys.modules[alias]
        raise
    return module


def load_kind(cell: Cell):
    return _load_module(cell.bench_dir, "kinds", cell.kind)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             device: Optional[Dict] = None) -> Dict:
    """Drive one cell once, below the device gate: set-up, the measured
    window, the comparison with the plain reference, the result line.
    ``device`` is what :func:`require_chips` returned; the tests pass their
    CPU's record and read counts, never times."""
    device = dict(device or device_record(cell.chips))
    kind = load_kind(cell)
    compiles = CompileCounter()
    tracer = DeviceTrace(work_dir("trace")) if trace else None
    run = kind.drive(cell, seed=seed, seconds=seconds, compiles=compiles,
                     trace=tracer, process_start=PROCESS_START)
    log(f"window closed after {run['window_s']:.2f}s; state freed")
    # The program's state is freed inside drive(); the peak was read there,
    # before the reference touched the chip.
    device["memory_peak_bytes"] = run["memory_peak_bytes"]
    gc.collect()
    check = kind.check(cell, seed=seed, run=run)
    log("compared with the reference")
    metrics: Dict[str, Dict] = {}
    result = {"correct": bool(check["correct"]),
              "attempted": int(run["attempted"]),
              "failed": int(run["failed"])}
    if trace:
        reduced = tracer.reduce(cell.chips)
        ctx = {"cell": cell, "run": run, "trace": reduced,
               "peaks": _peaks_or_none(device, cell), "compiles": compiles}
        metrics = read_per_layer(cell, ctx)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown(
            tracer.on_trace_clock(run.get("host_spans", [])))
    else:
        for metric in cell.end_to_end():
            if metric["name"] in run["end_to_end"]:
                metrics[metric["name"]] = {
                    "value": float(run["end_to_end"][metric["name"]]),
                    "unit": metric["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["compared"] = check["compared"]       # comes last in the line
    return result


def _peaks_or_none(device: Dict, cell: Cell) -> Optional[Dict]:
    """Peaks of the device the run was on; a CPU rehearsal has none, and a
    reader that needs a peak then returns nothing."""
    try:
        return peaks_for(device["kind"])
    except BenchmarkError:
        return None


def judge(cell: Cell, numbers: Dict[str, float]) -> Dict:
    """``correct``: every number compared is at or under its limit.  The
    limits are the cell's own, in ``limits/<cell>.json``; a number with no
    limit there is not compared (PERF.md says which and why)."""
    import math

    limits = load_json(os.path.join(cell.bench_dir, "limits",
                                    cell.name + ".json"))
    compared = {k: {"value": float(v), "limit": float(limits[k])}
                for k, v in numbers.items() if k in limits}
    correct = bool(compared) and all(
        math.isfinite(p["value"]) and p["value"] <= p["limit"]
        for p in compared.values())
    return {"correct": correct, "compared": compared}


def print_compared(compared: Dict, stream=sys.stderr) -> None:
    """Each number compared beside its limit, as the last lines."""
    for name, pair in compared.items():
        verdict = "ok" if pair["value"] <= pair["limit"] else "OVER"
        print(f"compared {name}: {pair['value']:.6g} limit "
              f"{pair['limit']:.6g} {verdict}", file=stream, flush=True)
