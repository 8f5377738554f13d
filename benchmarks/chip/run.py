"""Chip benchmark of the online-learning runtime: one cell of
`BENCHMARK.json`, one process, one run.

    python3 benchmarks/chip/run.py --workload mbv2.nc.etuner --seed 7 \
        --seconds 30 --trace 0

1. Refuses to run without a TPU, or with fewer chips than the cell asks.
2. Set-up (`setup_s`, from process start): `bootstrap()` (compile cache
   in `JAX_COMPILATION_CACHE_DIR`, else `<checkout>/.jax_cache/`), the
   benchmark's weights from the seed, the cell's stream and timeline, and
   one whole warm-up session, which builds every program the window uses.
3. Window: fresh sessions on the same stream and events, back to back,
   each ended by `block_until_ready` on the trained params, until
   `--seconds` have passed; the session in flight finishes. The
   end-to-end metrics cover every completed session.
4. `--trace 1`: the same untraced window, then one more session under
   `jax.profiler`; the per-layer metrics (`metrics/<name>.py`) read the
   trace, the traced session and the untraced window as the harness
   recorded them.
5. Check: one of the window's first two sessions, drawn from the seed,
   keeps what the check compares and is held against the plain
   reference (`refcheck.py`) once the window has closed; each number and
   its limit go to stderr and under "check" in the result.

The last line of stdout is one JSON object: correct, attempted, failed,
metrics, device (and breakdown with --trace 1), then check.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import harness  # noqa: E402


# the check compares one of the window's first sessions
KEPT_AMONG = 2


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def require_chips(n: int):
    """The TPU devices; exits non-zero without a TPU or with fewer than
    `n` chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"benchmark: JAX found no TPU (platform "
                         f"{devices[0].platform!r}); nothing was run")
    if len(devices) < n:
        raise SystemExit(f"benchmark: the cell asks for {n} chips, JAX "
                         f"found {len(devices)}")
    return devices[:n]


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def percentile(values, q):
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), q))


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def per_layer_specs(bench: dict, cell: str):
    return [m for m in bench["per_layer"]
            if "workloads" not in m or cell in m["workloads"]]


def end_to_end_specs(bench: dict, cell: str):
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def traced_window(session, directory):
    """One session under the profiler; returns (its log, the trace, the
    window's [lo, hi) in trace nanoseconds)."""
    import jax

    import tracereduce

    jax.profiler.start_trace(directory)
    try:
        traced = session.run()
    finally:
        jax.profiler.stop_trace()
    trace = tracereduce.load(tracereduce.find_xplane(directory))
    build = [s for s in trace.spans if s[0] == "bench/session_build"]
    run = [s for s in trace.spans if s[0] == "bench/session_run"]
    lo = build[-1][1]
    hi = run[-1][1] + run[-1][2]
    return traced, trace, lo, hi


def breakdown(trace, lo, hi) -> dict:
    import tracereduce

    ops = tracereduce.time_by_name(trace.all_ops(), lo, hi)
    idle = tracereduce.idle_by_span(trace, lo, hi)
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, v / 1e9] for n, v in top],
            "idle_gaps": [[n, v / 1e9] for n, v in gaps]}


def check(cell, session, pick, index, count):
    """Hold the kept session (`pick`, session `index` of `count`) against
    the reference. Returns (correct, numbers, all readings, lines)."""
    import refcheck

    got = refcheck.observed(pick)
    want = refcheck.replay(cell.ref, cell.doc, session.params, pick)
    detail = refcheck.compare(got, want)
    log(f"check detail: {json.dumps(detail)}")
    numbers = {k: detail[k] for k in refcheck.NUMBERS if k in detail}
    ok, lines = refcheck.verdict(numbers, cell.doc["limits"])
    lines.insert(0, f"check: session {index} of {count}, "
                    f"{detail['calls']} train-step calls replayed, "
                    f"{detail['requests']} requests and "
                    f"{detail['kernel_calls']} of {pick.kernel_calls} CKA "
                    f"kernel calls compared")
    return ok, numbers, detail, lines


def measure(cell, seed: int, seconds: float, trace: bool, devices,
            bench: dict, out_dir: str) -> dict:
    """Set-up, window, metrics and check of one run; returns the result
    object (without printing it)."""
    import numpy as np

    compiles = harness.CompileLog()
    session = harness.Session(cell, seed, recorder_spans=trace)
    # the window session the check compares, drawn from the seed; the
    # warm-up session keeps as it does, so that set-up builds the
    # programs of keeping too
    index = int(np.random.default_rng(seed).integers(KEPT_AMONG))
    warm = session.run(keep=True)
    setup_s = time.perf_counter() - T_START
    at_setup = compiles.snapshot()
    log(f"set-up: {setup_s} s; warm-up session {warm.wall_s} s; "
        f"compiles {at_setup}")

    logs, metrics, extra = [], {}, {}
    t_end = time.perf_counter() + seconds
    while time.perf_counter() < t_end or len(logs) <= index:
        logs.append(session.run(keep=len(logs) == index))
    if trace:
        traced, tr, lo, hi = traced_window(session, os.path.join(
            out_dir, "trace"))
    window_compiles = compiles.programs - at_setup["programs"]
    dev = device_info(devices)

    every = logs + [traced] if trace else logs
    results = [lg.result for lg in every]
    requests = sum(len(lg.requests) for lg in every)
    served = sum(len(lg.logits) for lg in every)
    wall = sum(lg.wall_s for lg in logs)
    log(f"window: {len(logs)} sessions, {wall} s, {requests} requests "
        f"(traced session included), "
        f"{served} served, {window_compiles} programs built; rounds "
        f"{[r.rounds for r in results][:3]}, recompiles "
        f"{[r.recompiles for r in results][:3]}")
    lat = [x for lg in logs for x in lg.latencies_s]
    log(f"request_ms: p50 {percentile(lat, 50) * 1e3} p95 "
        f"{percentile(lat, 95) * 1e3} over {len(lat)}")
    if not trace:
        values = {
            "images_per_s": sum(session.images(lg) for lg in logs) / wall,
            "setup_s": setup_s,
        }
        for spec in end_to_end_specs(bench, cell.name):
            metrics[spec["name"]] = {"value": values[spec["name"]],
                                     "unit": spec["unit"]}
    else:
        import tracereduce

        busy = tracereduce.busy_ns(tr, lo, hi) / 1e9
        window_s = (hi - lo) / 1e9
        dev.update(busy_s=busy, window_s=window_s)
        peaks = harness.load_json(os.path.join(HERE, "peaks.json"))
        if dev["kind"] not in peaks["devices"]:
            raise SystemExit(f"benchmark: no peaks for device kind "
                             f"{dev['kind']!r} in peaks.json")
        ctx = MetricContext(cell=cell, session=session, log=traced,
                            window_logs=logs, trace=tr,
                            lo=lo, hi=hi, busy_s=busy, window_s=window_s,
                            setup=at_setup, window_compiles=window_compiles,
                            peak=peaks["devices"][dev["kind"]])
        for spec in per_layer_specs(bench, cell.name):
            reader = harness.load_module(
                os.path.join(HERE, "metrics", spec["name"] + ".py"),
                "metric_" + spec["name"].replace(".", "_"))
            value = reader.read(ctx)
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
        extra["breakdown"] = breakdown(tr, lo, hi)
        for note in ctx.notes:
            log(note)

    # the program's state goes before the reference runs
    del results, warm
    gc.collect()
    t_check = time.perf_counter()
    ok, numbers, detail, lines = check(cell, session, logs[index], index,
                                       len(logs))
    log(f"check took {time.perf_counter() - t_check} s")
    session.close()
    limits = cell.doc["limits"]
    return {"correct": ok, "attempted": requests, "failed": requests - served,
            "metrics": metrics, "device": dev, **extra,
            "check": {k: {"value": numbers.get(k), "limit": limits[k]}
                      for k in limits},
            "_lines": lines, "_detail": detail}


class MetricContext:
    """What a per-layer metric reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.notes = []

    def note(self, msg: str):
        self.notes.append(msg)


def main(argv=None) -> int:
    args = parse(argv)
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell = harness.load_cell(bench, args.workload)
    harness.use_program()
    from repro.launch.platform import bootstrap

    bootstrap()
    devices = require_chips(cell.chips)
    log(f"platform: {devices[0].platform}  device_kind: "
        f"{devices[0].device_kind}  devices: {len(devices)}")
    out_dir = os.path.join(harness.ROOT, ".bench_out",
                           f"{args.workload}.{args.seed}")
    result = measure(cell, args.seed, args.seconds, bool(args.trace),
                     devices, bench, out_dir)
    lines = result.pop("_lines")
    result.pop("_detail")
    for line in lines:
        log(line)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
