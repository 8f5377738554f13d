"""Device-to-host pulls on the hot path per image the stream delivered,
from the program's `host_syncs{site}` counters (`RunResult.host`) over
every session of the untraced window of the traced run. Each pull waits
for the device work queued before it. Sites: `validate` (the per-round
validation forward: accuracy and logits), `serve` (the logits of a
vmapped serving stack), `cka_unit` (each unit's CKA value),
`cka_shape` (each unit's feature map, read for its shape),
`cka_reference` (the reference features). Moves `images_per_s`."""

import programspans

DEVICE_OPS = ()


def read(ctx):
    hs = programspans.hosts(ctx.window_logs)
    images = sum(ctx.session.images(lg) for lg in ctx.window_logs)
    if hs is None or not images:
        return None
    by = {}
    for h in hs:
        for key, v in h["counters"].items():
            name, labels = programspans.labels(key)
            if name == "host_syncs":
                site = labels.get("site", "")
                by[site] = by.get(site, 0.0) + v
    ctx.note(f"host_syncs_per_image: pulls by site over {len(hs)} "
             f"sessions: {dict(sorted(by.items()))}")
    return sum(by.values()) / images
