"""Device time of the train-step programs per labelled batch trained, in
ms: the "XLA Modules" events of the fused train scan (`jit_multi`, the
scan `TrainStepCache.fused_call` runs) in the traced session, over the
batches its calls trained (pretraining, rounds and replay batches;
padding steps of a scan bucket are not batches). Moves `images_per_s`."""

DEVICE_OPS = ("jit_multi",)


def read(ctx):
    mods = [e for e in ctx.trace.all_modules()
            if e[0].split("(")[0] in DEVICE_OPS and ctx.lo <= e[1] < ctx.hi]
    batches = sum(len(c.batches) for c in ctx.log.calls)
    if not mods or not batches:
        return None
    return sum(d for _, _, d in mods) / 1e6 / batches
