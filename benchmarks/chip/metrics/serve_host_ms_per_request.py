"""Host time of serving, in ms per request served, from the program's own
spans (`RunResult.host`) over every session of the untraced window of the
traced run: the total time of the outermost `serve/*` spans
(`serve/submit`; `serve/drain` with `serve/stage`: concatenation,
stacking and upload, `serve/forward`: the vmapped dispatch and the logits
pulled to the host, `serve/score`: host argmax, accuracy and the policy's
`on_served`) over the requests those sessions served. Moves
`images_per_s`."""

import programspans

DEVICE_OPS = ()


def read(ctx):
    hs = programspans.hosts(ctx.window_logs)
    served = sum(len(lg.logits) for lg in ctx.window_logs)
    if hs is None or not served:
        return None
    total, _ = programspans.outermost(hs, "serve/")
    return 1e3 * total / served
