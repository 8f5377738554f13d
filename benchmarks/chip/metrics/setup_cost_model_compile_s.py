"""Compile-path seconds (tracing, lowering, compiling) that set-up spends
inside the program's `round/cost` spans, where `TrainStepCache.flops`
lowers and compiles a train step per freeze plan for the modelled cost
and nothing runs the program: the process's `compile_s{span,stage}`
counters (`repro.obs.host`) less the deltas of the window's sessions and
of the traced session (`RunResult.host`). Notes set-up's compile seconds
by span, and the window sessions' compiles by span (0 in a sound run).
Moves `setup_s`."""

import programspans

DEVICE_OPS = ()


def _by_span(counters, name):
    out = {}
    for key, v in counters.items():
        n, labels = programspans.labels(key)
        if n == name:
            out[labels["span"]] = out.get(labels["span"], 0.0) + v
    return out


def read(ctx):
    try:
        from repro.obs import host
    except ImportError:
        return None
    hs = programspans.hosts(ctx.window_logs + [ctx.log])
    if hs is None:
        return None
    setup = _by_span(host.snapshot()["counters"], "compile_s")
    for h in hs:
        for span, v in _by_span(h["counters"], "compile_s").items():
            setup[span] = setup.get(span, 0.0) - v
    window = {}
    for h in hs[:-1]:
        for span, v in _by_span(h["counters"], "compiles").items():
            window[span] = window.get(span, 0) + v
    top = sorted(setup.items(), key=lambda kv: -kv[1])[:12]
    ctx.note("setup_cost_model_compile_s: set-up compile s by span: "
             + ", ".join(f"{k} {v:.3f}" for k, v in top)
             + f"; window sessions' compiles by span: {window}")
    return sum(v for span, v in setup.items()
               if "round/cost" in programspans.names(span))
