"""From a profiler trace to numbers: busy and idle, per-program time, gaps.

A TPU trace (``.xplane.pb``, read with ``jax.profiler.ProfileData`` alone)
has one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Modules``
holds one event per run of a compiled program (``jit_step(<hash>)``) and
whose line ``XLA Ops`` holds one event per HLO instruction (the event's name
is the instruction's text).  Host threads are lines of ``/host:CPU``; the
``python`` line carries ``TraceAnnotation`` spans.  Times are nanoseconds
from the start of the profiler session.

The reduction works on a plain list of events, so a recorded trace can be
kept as JSON beside the tests.
"""

from __future__ import annotations

import re
import statistics
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, str, str, float, float]   # plane, line, name, start, dur

DEVICE_PREFIX = "/device:TPU:"
MODULES, OPS = "XLA Modules", "XLA Ops"
ANCHOR = "bench/anchor"


def load_xplane(path: str) -> List[Event]:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    return [(plane.name, line.name, ev.name, float(ev.start_ns),
             float(ev.duration_ns))
            for plane in data.planes for line in plane.lines
            for ev in line.events]


def program_name(module_event_name: str) -> str:
    """``jit__spec_round(123456)`` -> ``jit__spec_round``."""
    return re.sub(r"\(\d+\)$", "", module_event_name)


def op_label(hlo_text: str) -> str:
    """A short stable label for an HLO instruction: opcode, the
    instruction's base name and its result shape, e.g.
    ``fusion_multiply_reduce_fusion_f32_24_4100_32_``."""
    m = re.match(r"%?([\w.\-]+) = \(?(\w+)\[([\d,]*)\][^ ]* ([\w\-]+)\(",
                 hlo_text)
    if not m:
        return re.sub(r"\W+", "_", hlo_text[:48])
    name, dtype, dims, opcode = m.groups()
    base = re.sub(r"[.\d]+$", "", name)
    label = f"{opcode}_{base}_{dtype}_{dims.replace(',', '_')}_"
    return re.sub(r"\W+", "_", label)


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of ``(start_ns, end_ns)`` intervals, in seconds."""
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total / 1e9


class Reduced:
    """What the readers read.  ``chips`` = how many device planes count."""

    def __init__(self, events: Sequence[Event], chips: int = 1,
                 window_ns: Optional[Tuple[float, float]] = None) -> None:
        planes = sorted({e[0] for e in events if e[0].startswith(DEVICE_PREFIX)},
                        key=lambda p: int(p[len(DEVICE_PREFIX):]))[:chips]
        self.planes = planes
        self.events = list(events)
        self._ops = {p: sorted((e for e in events if e[0] == p and e[1] == OPS),
                               key=lambda e: e[3]) for p in planes}
        self._modules = {p: sorted((e for e in events
                                    if e[0] == p and e[1] == MODULES),
                                   key=lambda e: e[3]) for p in planes}
        if window_ns is None:
            spans = [(e[3], e[3] + e[4]) for p in planes
                     for e in self._modules[p] + self._ops[p]]
            window_ns = ((min(s for s, _ in spans), max(t for _, t in spans))
                         if spans else (0.0, 0.0))
        self.window_ns = window_ns

    # -- the device as a whole ------------------------------------------

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.planes:
            return 0.0
        per_chip = [union_seconds((e[3], e[3] + e[4]) for e in self._ops[p])
                    for p in self.planes]
        return sum(per_chip) / len(per_chip)

    @property
    def idle_share(self) -> Optional[float]:
        if self.window_s <= 0 or not self.planes:
            return None
        return max(0.0, 1.0 - self.busy_s / self.window_s)

    # -- programs -------------------------------------------------------

    def program_seconds(self, program: str, plane: int = 0) -> List[float]:
        """Device duration of every run of programs whose name contains
        ``program``, in seconds."""
        if not self.planes:
            return []
        return [e[4] / 1e9 for e in self._modules[self.planes[plane]]
                if program in program_name(e[2])]

    def program_gaps(self, program: str, plane: int = 0) -> List[float]:
        """Idle seconds between the end of one program on the device and
        the start of the next, for every gap that ends at a run of
        ``program``."""
        if not self.planes:
            return []
        mods = self._modules[self.planes[plane]]
        return [max(0.0, (b[3] - (a[3] + a[4])) / 1e9)
                for a, b in zip(mods, mods[1:])
                if program in program_name(b[2])]

    def op_seconds(self, pattern: str, plane: int = 0) -> List[float]:
        """Device duration of every HLO instruction whose text matches the
        regular expression ``pattern``."""
        if not self.planes:
            return []
        rx = re.compile(pattern)
        return [e[4] / 1e9 for e in self._ops[self.planes[plane]]
                if rx.search(e[2])]

    # -- the breakdown --------------------------------------------------

    def top_ops(self, n: int = 10) -> List[List]:
        total: Dict[str, float] = defaultdict(float)
        for p in self.planes[:1]:
            for e in self._ops[p]:
                total[op_label(e[2])] += e[4] / 1e9
        return [[k, v] for k, v in
                sorted(total.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, host_spans: Sequence[Tuple[str, float, float]] = (),
                  n: int = 10) -> List[List]:
        """The idle time between programs, summed by what the host was
        doing: the host span (name, start_ns, end_ns on the trace's clock)
        that covers the middle of the gap, else the two programs the gap
        lies between."""
        total: Dict[str, float] = defaultdict(float)
        for p in self.planes[:1]:
            mods = self._modules[p]
            for a, b in zip(mods, mods[1:]):
                lo, hi = a[3] + a[4], b[3]
                if hi <= lo:
                    continue
                mid = (lo + hi) / 2
                label = None
                best = None
                for name, s, t in host_spans:
                    if s <= mid <= t and (best is None or t - s < best):
                        label, best = name, t - s
                if label is None:
                    label = (f"{program_name(a[2])}_-_"
                             f"{program_name(b[2])}")
                total[re.sub(r"[^\w\-./]+", "_", label)] += (hi - lo) / 1e9
        return [[k, v] for k, v in
                sorted(total.items(), key=lambda kv: -kv[1])[:n]]

    def breakdown(self, host_spans: Sequence[Tuple[str, float, float]] = ()
                  ) -> Dict[str, List]:
        return {"device_ops": self.top_ops(),
                "idle_gaps": self.idle_gaps(host_spans)}

    # -- clocks ---------------------------------------------------------

    def anchor_ns(self) -> Optional[float]:
        """Trace time of the harness's ``bench/anchor`` annotation, which
        the harness also stamped on ``perf_counter_ns``: the difference
        puts the program's own spans on the trace's clock."""
        for e in self.events:
            if e[2] == ANCHOR:
                return e[3]
        return None


def p50(values: Sequence[float]) -> Optional[float]:
    return float(statistics.median(values)) if values else None
