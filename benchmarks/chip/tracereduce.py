"""Reduction of a profiler trace to what the per-layer metrics read.

`load(path)` reads the `.xplane.pb` that `jax.profiler` writes, with
nothing but JAX: on each device plane (`/device:...`) the line of XLA
operations and the line of XLA modules (whole programs), and on the host
the benchmark's own spans (`bench/...`, from `jax.profiler
.TraceAnnotation`). Times are nanoseconds on the profiler's one clock.

The reductions:
- `busy_ns`: the union of the device's operation intervals inside the
  window, averaged over the devices that ran any;
- `time_by_name`: device time per operation or module name;
- `idle_by_span`: each idle gap of the device inside the window, given to
  the innermost host span that covers its midpoint ("other" if none).
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench/"

Event = Tuple[str, int, int]   # (name, start_ns, duration_ns)


@dataclass
class Trace:
    ops: Dict[str, List[Event]] = field(default_factory=dict)      # per device
    modules: Dict[str, List[Event]] = field(default_factory=dict)  # per device
    spans: List[Event] = field(default_factory=list)               # host

    def all_ops(self) -> List[Event]:
        return [e for evs in self.ops.values() for e in evs]

    def all_modules(self) -> List[Event]:
        return [e for evs in self.modules.values() for e in evs]


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def op_name(name: str) -> str:
    """A device op's name as the trace gives it may be its whole HLO
    instruction ("%cka_terms.1 = (f32[1,1]...) custom-call(...)"): keep
    the instruction's name ("cka_terms.1")."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    trace = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                evs = [(op_name(e.name), int(e.start_ns),
                        int(e.duration_ns)) for e in line.events]
                if line.name == OPS_LINE:
                    trace.ops.setdefault(plane.name, []).extend(evs)
                elif line.name == MODULES_LINE:
                    trace.modules.setdefault(plane.name, []).extend(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                trace.spans.extend((e.name, int(e.start_ns), int(e.duration_ns))
                                   for e in line.events
                                   if e.name.startswith(SPAN_PREFIX))
    return trace


def _merged(events: List[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Union of the events' intervals, clipped to [lo, hi), in order."""
    ivs = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events
                 if s < hi and s + d > lo and d > 0)
    out: List[List[int]] = []
    for s, e in ivs:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(trace: Trace, lo: int, hi: int) -> float:
    """Busy time of the window [lo, hi), averaged over the devices that
    ran an operation."""
    per = [sum(e - s for s, e in _merged(evs, lo, hi))
           for evs in trace.ops.values() if evs]
    return sum(per) / len(per) if per else 0.0


def time_by_name(events: List[Event], lo: int, hi: int) -> Dict[str, int]:
    """Device time per name, of the events that start inside [lo, hi)."""
    out: Dict[str, int] = {}
    for name, s, d in events:
        if lo <= s < hi:
            out[name] = out.get(name, 0) + d
    return out


def idle_gaps(trace: Trace, lo: int, hi: int) -> List[Tuple[int, int]]:
    """Idle intervals of the busiest device inside [lo, hi)."""
    if not trace.ops:
        return [(lo, hi)]
    evs = max(trace.ops.values(), key=len)
    gaps, t = [], lo
    for s, e in _merged(evs, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def idle_by_span(trace: Trace, lo: int, hi: int) -> Dict[str, int]:
    """Idle time per innermost enclosing host span (by the gap's
    midpoint)."""
    out: Dict[str, int] = {}
    for s, e in idle_gaps(trace, lo, hi):
        mid = (s + e) // 2
        inner = [(d, name) for name, st, d in trace.spans
                 if st <= mid < st + d]
        name = min(inner)[1][len(SPAN_PREFIX):] if inner else "other"
        out[name] = out.get(name, 0) + (e - s)
    return out
