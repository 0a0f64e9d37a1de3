"""sim.executables: sweep executables the process compiled or read from
the persistent cache (``repro.core.sweep.compile_count``, a program
counter). The window compiles none, so these are the programs one grid
run dispatches to, warmed in set-up."""


def read(ctx):
    if ctx["family"] == "sim":
        from repro.core import sweep
        return sweep.compile_count()
    return None
