"""sim.device_ns_per_req: device-busy nanoseconds in the traced window,
summed over the cell's devices, per simulated request completed in it."""


def read(ctx):
    tr = ctx["trace"]
    if ctx["family"] == "sim" and tr and ctx["requests"]:
        return 1e9 * sum(tr["busy_s"]) / ctx["requests"]
    return None
