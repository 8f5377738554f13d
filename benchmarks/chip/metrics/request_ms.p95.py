"""95th percentile, in ms, of every request's host time in the untraced
window of the traced run (`--seconds` of sessions, as a `--trace 0` run
measures them; the session under the profiler after it is left out): from the runtime
taking the request up (`InferenceServer.submit`) to the end of the
`drain` that scored it. It includes deferral to the segment's drain and
waiting behind train work queued on the device. A per-layer metric, not
an end-to-end one: the tail is set by which requests of a session queue
behind a round or a CKA pass, which the seed decides (LazyTune and
SimFreeze decide on the seed's weights and images): on one TPU v5e it
read 18.5 to 93.3 ms across seeds while two runs of one seed agreed
within 4%.
Moves `images_per_s`."""

import numpy as np

DEVICE_OPS = ()


def read(ctx):
    lat = [x for lg in ctx.window_logs for x in lg.latencies_s]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
