"""Share of the traced window in which no operation ran on the device:
1 - (union of the "XLA Ops" intervals) / window, in %. The window is one
whole session, from its build to the end of its run. Moves
`images_per_s`."""

DEVICE_OPS = ("*",)


def read(ctx):
    if ctx.window_s <= 0 or ctx.busy_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
