"""setup_s: process start to the start of the window (host clock):
imports, TPU start, traffic from the seed, compile or cache read, and
the warm-up unit."""


def read(ctx):
    return ctx["setup_s"]
