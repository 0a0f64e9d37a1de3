"""sim.compile_s: seconds of backend compiles or persistent-cache reads
JAX reports during set-up (``/jax/core/compile/backend_compile_duration``)."""


def read(ctx):
    if ctx["family"] == "sim":
        return ctx["setup_compile_s"]
    return None
