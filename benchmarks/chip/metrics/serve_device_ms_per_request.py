"""Device time of the predict programs per request served, in ms: the
"XLA Modules" events named `jit_predict` in the traced session (the
vmapped serving forward, and the per-round validation forward, which
shares the name), over the requests the session served. Moves
`request_ms.p95`."""

DEVICE_OPS = ("jit_predict",)


def read(ctx):
    mods = [e for e in ctx.trace.all_modules()
            if e[0].split("(")[0] in DEVICE_OPS and ctx.lo <= e[1] < ctx.hi]
    served = len(ctx.log.logits)
    if not mods or not served:
        return None
    return sum(d for _, _, d in mods) / 1e6 / served
