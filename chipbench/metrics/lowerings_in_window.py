"""JAX lowerings of a jaxpr to an MLIR module counted inside the window
(``jax.monitoring`` event ``/jax/core/compile/jaxpr_to_mlir_module_duration``)."""


def read(ctx):
    return ctx.window.lowerings
