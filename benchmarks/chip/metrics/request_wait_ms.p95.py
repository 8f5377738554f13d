"""95th percentile, in ms, of every request's host wait in the untraced
window of the traced run, from the program's `request_wait_s` histogram
(`RunResult.host`): from `InferenceServer.submit` to the dispatch of the
forward that answers the request (the deferral to its segment's drain,
and the staging before it). The service after the dispatch is not in it;
`request_ms.p95` holds both, read by the harness. Moves `images_per_s`."""

import numpy as np

import programspans

DEVICE_OPS = ()


def read(ctx):
    hs = programspans.hosts(ctx.window_logs)
    waits = programspans.samples(hs, "request_wait_s") if hs else []
    return float(np.percentile(waits, 95)) * 1e3 if waits else None
