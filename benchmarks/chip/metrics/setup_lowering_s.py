"""Seconds spent lowering jitted functions to MLIR during set-up, from
jax.monitoring's `/jax/core/compile/jaxpr_to_mlir_module_duration`
events. The persistent compilation cache does not save this work, so it
is paid by every run. Moves `setup_s`."""

DEVICE_OPS = ()


def read(ctx):
    return ctx.setup["lowering"]
