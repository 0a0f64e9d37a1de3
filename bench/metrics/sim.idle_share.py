"""sim.idle_share: percent of the traced window in which no operation
ran on the device, averaged over the cell's devices."""


def read(ctx):
    tr = ctx["trace"]
    if ctx["family"] == "sim" and tr:
        busy = sum(tr["busy_s"]) / len(tr["busy_s"])
        return 100.0 * (1.0 - busy / tr["window_s"])
    return None
