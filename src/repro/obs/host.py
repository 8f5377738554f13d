"""Host spans and counters on the program's own clock.

The modelled `Tracer` (trace.py) records the Jetson timeline the cost
model predicts; this module records where the *host* spends real time,
always on:

- `span(name, **args)` opens `jax.profiler.TraceAnnotation("edgeol/<name>")`
  (so under a profiler the span lands on the same clock as the device ops
  of the `.xplane.pb`) and adds its `perf_counter` seconds to a
  process-wide table keyed by **span path**: the names of the open spans,
  outermost first, joined by `PATH_SEP` ("event/data>round>train/dispatch").
  Each path keeps its count, total seconds and self seconds (total less
  what child spans cover).
- `count(name, n, **labels)` and `observe(name, value, **labels)` write
  counters and exact-sample histograms into `REGISTRY`, a
  `MetricsRegistry` (metrics.py).
- A `jax.monitoring` listener, registered once per process at import,
  charges every compile-path duration (tracing, lowering, compiling) to
  `compile_s{span=<innermost open path>,stage=...}` and every backend
  compile (a build or a persistent-cache load) to `compiles{span=...}`.

`snapshot()` copies the whole state; `since(mark)` is what happened after
a snapshot (`DeviceFleet.run` stores it as `RunResult.host`).

Rule: a span never adds a device-to-host sync. Where a span already
contains a pull, the span records the wait and `host_syncs{site}` counts
the pull. The spans are single-threaded, like the runtime's event loop.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import jax

from repro.obs.metrics import MetricsRegistry

PREFIX = "edgeol/"
PATH_SEP = ">"
#: path label of compile events outside every span
OUTSIDE = "(none)"

REGISTRY = MetricsRegistry()
# span path -> [count, total seconds, self seconds]
_SPANS: Dict[str, List[float]] = {}
# the open spans, innermost last
_OPEN: List["span"] = []

_COMPILE_STAGES = {"/jax/core/compile/jaxpr_trace_duration": "tracing",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowering",
                   "/jax/core/compile/backend_compile_duration": "compiling"}


class span:
    """Context manager: one host span (module docstring)."""

    __slots__ = ("name", "path", "_ann", "_t0", "_child")

    def __init__(self, name: str, **args: Any):
        self.name = name
        self._ann = jax.profiler.TraceAnnotation(PREFIX + name, **args)

    def __enter__(self) -> "span":
        self.path = _OPEN[-1].path + PATH_SEP + self.name if _OPEN \
            else self.name
        self._ann.__enter__()
        _OPEN.append(self)
        self._child = 0.0
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        _OPEN.pop()
        if _OPEN:
            _OPEN[-1]._child += dt
        rec = _SPANS.get(self.path)
        if rec is None:
            rec = _SPANS[self.path] = [0, 0.0, 0.0]
        rec[0] += 1
        rec[1] += dt
        rec[2] += dt - self._child
        self._ann.__exit__(*exc)


def current_path() -> str:
    """Path of the innermost open span (`OUTSIDE` if none)."""
    return _OPEN[-1].path if _OPEN else OUTSIDE


def count(name: str, n: float = 1, **labels: Any) -> None:
    REGISTRY.counter(name, **labels).inc(n)


def observe(name: str, value: float, **labels: Any) -> None:
    REGISTRY.histogram(name, **labels).observe(value)


def snapshot() -> Dict[str, Any]:
    """A copy of everything recorded so far in this process."""
    return {"spans": {p: {"count": int(r[0]), "total_s": r[1], "self_s": r[2]}
                      for p, r in _SPANS.items()},
            "counters": REGISTRY.counter_values(),
            "histograms": REGISTRY.histogram_samples()}


def since(mark: Dict[str, Any], now: Optional[Dict[str, Any]] = None
          ) -> Dict[str, Any]:
    """What was recorded between the snapshot `mark` and `now` (default:
    a fresh snapshot): span counts and seconds, counter increments and the
    histogram samples added, each left out where nothing happened."""
    now = snapshot() if now is None else now
    zero = {"count": 0, "total_s": 0.0, "self_s": 0.0}
    spans = {}
    for p, r in now["spans"].items():
        r0 = mark["spans"].get(p, zero)
        if r["count"] != r0["count"]:
            spans[p] = {k: r[k] - r0[k] for k in r}
    counters = {k: v - mark["counters"].get(k, 0.0)
                for k, v in now["counters"].items()
                if v != mark["counters"].get(k, 0.0)}
    histograms = {k: v[len(mark["histograms"].get(k, ())):]
                  for k, v in now["histograms"].items()
                  if len(v) > len(mark["histograms"].get(k, ()))}
    return {"spans": spans, "counters": counters, "histograms": histograms}


def _on_duration(event: str, duration: float, **_: Any) -> None:
    stage = _COMPILE_STAGES.get(event)
    if stage is None:
        return
    where = current_path()
    count("compile_s", duration, span=where, stage=stage)
    if stage == "compiling":
        count("compiles", 1, span=where)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
