"""Host time of SimFreeze's CKA probe, in ms per probing pass, from the
program's own spans (`RunResult.host`) over every session of the untraced
window of the traced run: the total time of the outermost `cka/*` spans
(`cka/reference`: the reference model's features to the host at a
scenario's start or change; `cka/pass`: `_all_cka`, each unit's CKA
value and feature map pulled to the host) over the `cka/pass` spans.
Moves `images_per_s`."""

import programspans

DEVICE_OPS = ()


def read(ctx):
    hs = programspans.hosts(ctx.window_logs)
    if hs is None:
        return None
    total, counts = programspans.outermost(hs, "cka/")
    passes = counts.get("cka/pass", 0)
    return 1e3 * total / passes if passes else None
