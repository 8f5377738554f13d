"""XLA programs built (compiled, or loaded from the persistent cache)
after set-up ended, counted from jax.monitoring's backend-compile
events. Set-up builds every program the window uses, so this is 0 in a
sound run. Moves `images_per_s`."""

DEVICE_OPS = ()


def read(ctx):
    return ctx.window_compiles
