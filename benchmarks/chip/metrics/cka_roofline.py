"""Share of its roofline that SimFreeze's CKA kernel reached in the
traced session, in %.

The kernel computes the Gram terms of two centred [n, d] activations of
one freeze unit (n probe examples, d = the unit's output features). The
benchmark's own count of what the call needs: 4 n^2 d FLOPs (the two
n x n Grams) and 8 n d bytes (each matrix read once, float32). The least
time is the larger of FLOPs over the bf16 peak and bytes over the HBM
peak; the share is the sum of those least times over the kernel's
device time (its "XLA Ops" events, `cka_terms.<k>`). A probing pass runs
the kernel once per unit, in unit order, so the k-th event belongs to
unit k mod (units); where the count does not fit, nothing is read.
Moves `images_per_s`."""

import re

DEVICE_OPS = ("cka_terms",)
_NAME = re.compile(r"^cka_terms(\.\d+)?$")


def read(ctx):
    evs = sorted((e for e in ctx.trace.all_ops()
                  if _NAME.match(e[0]) and ctx.lo <= e[1] < ctx.hi),
                 key=lambda e: e[1])
    sizes = ctx.cell.ref.unit_feature_sizes(ctx.cell.doc)
    if not evs or len(evs) % len(sizes):
        return None
    n = ctx.cell.mix["stream"]["batch_size"]
    flops = bytes_ = least = 0.0
    for k, _ in enumerate(evs):
        d = sizes[k % len(sizes)]
        f, b = 4.0 * n * n * d, 8.0 * n * d
        flops, bytes_ = flops + f, bytes_ + b
        least += max(f / ctx.peak["bf16_flops_per_s"],
                     b / ctx.peak["hbm_bytes_per_s"])
    spent = sum(d for _, _, d in evs) / 1e9
    bound = "memory" if bytes_ / ctx.peak["hbm_bytes_per_s"] > \
        flops / ctx.peak["bf16_flops_per_s"] else "compute"
    ctx.note(f"cka_roofline: {len(evs)} kernel calls, {spent} s on the "
             f"device, {flops} FLOPs and {bytes_} bytes needed, "
             f"{bound}-bound")
    return 100.0 * least / spent
