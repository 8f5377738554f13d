"""The paper's average inference accuracy over every request of the
untraced window of the traced run: the share of served images whose top
logit is their label, averaged per request. It keeps a policy change honest (a policy
that trains less buys `images_per_s` with accuracy). It is a per-layer
metric and not an end-to-end one because it moves with the seed (random
weights, random images) far more than a bound of 25% holds: across 12
seeds of `mbv2.nc.etuner` on one TPU v5e it read 0.108 to 0.281. Moves `images_per_s`."""

import numpy as np

DEVICE_OPS = ()


def read(ctx):
    hits = [np.mean(np.argmax(lg, -1) == np.asarray(r.labels))
            for session in ctx.window_logs
            for lg, r in zip(session.logits, session.requests)]
    return float(np.mean(hits)) if hits else None
