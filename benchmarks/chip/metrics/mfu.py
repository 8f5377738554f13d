"""Model FLOP/s utilization of the traced session, in %: the model FLOPs
the session needed over (window seconds x the chip's bf16 peak).

FLOPs come from the configuration's own analytic count
(`configs/<config>.py: unit_forward_flops`). Per labelled image of each
train-step call, under the plan the call trained with: a trained unit
counts forward and backward (3x its forward), a frozen unit that the
gradient still flows through (after the first trained unit) counts
forward and its input gradient (2x), a frozen unit before it counts its
forward (1x). Per served image and per image of the per-round
validation forward: the whole forward. Padding steps of a scan bucket
and the CKA probe forwards are not counted. The program's float32
matmuls at default precision run on the MXU in bf16, hence that peak.
Moves `images_per_s`."""

DEVICE_OPS = ()


def train_flops_per_image(fwd, flags):
    if flags is None:
        flags = (False,) * len(fwd)
    total, flowing = 0.0, False
    for f, frozen in zip(fwd, flags):
        if not frozen:
            total, flowing = total + 3.0 * f, True
        else:
            total += (2.0 if flowing else 1.0) * f
    return total


def read(ctx):
    fwd = ctx.cell.ref.unit_forward_flops(ctx.cell.doc)
    whole = sum(fwd)
    flops = 0.0
    for call in ctx.log.calls:
        per = train_flops_per_image(fwd, call.flags)
        flops += per * sum(len(b["labels"]) for b in call.batches)
    flops += whole * sum(len(r.labels) for r in ctx.log.requests)
    val = ctx.cell.mix["stream"]["batch_size"]
    flops += whole * val * (len(ctx.log.calls) - 1)
    if ctx.window_s <= 0:
        return None
    return 100.0 * flops / (ctx.window_s * ctx.peak["bf16_flops_per_s"])
