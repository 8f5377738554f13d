"""What the program records about its own host time, for the per-layer
readers: the spans and counters of `repro.obs.host` as each session's
`RunResult.host` holds them, and the program's `edgeol/` host spans in the
profiler trace of the traced session.

A program without host spans records none of this: every function then
returns None or an empty list, and the reader reads nothing.

`RunResult.host` (one session): "spans" maps a span path (names joined by
">", outermost first) to its count, total and self seconds; "counters"
maps a rendered counter (`name{label=value,...}`) to its value;
"histograms" maps a rendered histogram to its samples.
"""
from __future__ import annotations

import bisect
import os
from typing import Dict, List, Optional, Tuple

import harness
import tracereduce

SEP = ">"
PREFIX = "edgeol/"

Event = Tuple[str, int, int]   # (name, start_ns, duration_ns)


def hosts(logs) -> Optional[List[dict]]:
    """Each session's `RunResult.host`, or None if any session has none."""
    out = [getattr(lg.result, "host", None) for lg in logs]
    return out if out and all(out) else None


def names(path: str) -> List[str]:
    return path.split(SEP)


def outermost(hs: List[dict], prefix: str) -> Tuple[float, Dict[str, int]]:
    """Total seconds of the spans named `prefix...` that lie under no
    other such span, and the count of every such span by name."""
    total, counts = 0.0, {}
    for h in hs:
        for path, s in h["spans"].items():
            parts = names(path)
            if not parts[-1].startswith(prefix):
                continue
            counts[parts[-1]] = counts.get(parts[-1], 0) + s["count"]
            if not any(p.startswith(prefix) for p in parts[:-1]):
                total += s["total_s"]
    return total, counts


def labels(key: str) -> Tuple[str, Dict[str, str]]:
    """`name{a=1,b=x}` -> ("name", {"a": "1", "b": "x"})."""
    if "{" not in key:
        return key, {}
    name, inner = key[:-1].split("{", 1)
    return name, dict(kv.split("=", 1) for kv in inner.split(","))


def counter_sum(counters: Dict[str, float], name: str, **want) -> float:
    """Sum of the counters called `name` whose labels include `want`."""
    total = 0.0
    for key, v in counters.items():
        n, ls = labels(key)
        if n == name and all(ls.get(k) == w for k, w in want.items()):
            total += v
    return total


def samples(hs: List[dict], name: str) -> List[float]:
    return [x for h in hs for key, v in h["histograms"].items()
            if labels(key)[0] == name for x in v]


def trace_dir(ctx) -> str:
    """Where `run.py` has the profiler write the traced session."""
    return os.path.join(harness.ROOT, ".bench_out",
                        f"{ctx.cell.name}.{ctx.session.seed}", "trace")


def trace_spans(path: str, lo: int, hi: int) -> List[Event]:
    """The program's host spans in the `.xplane.pb` at `path` that overlap
    [lo, hi), named without the prefix (and without any `#k=v#`
    arguments)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if not e.name.startswith(PREFIX):
                    continue
                s, d = int(e.start_ns), int(e.duration_ns)
                if s < hi and s + d > lo:
                    out.append((e.name[len(PREFIX):].split("#", 1)[0], s, d))
    return out


def innermost(spans: List[Event]) -> List[Tuple[int, int, str]]:
    """Cut the time the spans cover into [start, end) pieces, each named
    after the innermost span open over it. The spans of one thread nest,
    so a stack sweep in start order gives it."""
    pieces: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, str]] = []   # (end, name), innermost last
    t = 0

    def cut(upto: int):
        nonlocal t
        if stack and upto > t:
            pieces.append((t, upto, stack[-1][1]))
        t = max(t, upto)

    for name, s, d in sorted(spans, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            cut(stack[-1][0])
            stack.pop()
        cut(s)
        stack.append((s + d, name))
    while stack:
        cut(stack[-1][0])
        stack.pop()
    return pieces


def idle_by_program_span(trace, spans: List[Event], lo: int, hi: int
                         ) -> Tuple[Dict[str, int], int]:
    """Each idle gap of the device in [lo, hi), given to the innermost
    program span over its midpoint: (ns by span name, ns under none)."""
    pieces = innermost(spans)
    starts = [p[0] for p in pieces]
    by: Dict[str, int] = {}
    outside = 0
    for s, e in tracereduce.idle_gaps(trace, lo, hi):
        mid = (s + e) // 2
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and pieces[i][0] <= mid < pieces[i][1]:
            by[pieces[i][2]] = by.get(pieces[i][2], 0) + (e - s)
        else:
            outside += e - s
    return by, outside
