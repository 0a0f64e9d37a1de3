"""sim_req_per_s: simulated L1 requests (points x rounds x cores x m)
of every grid run completed in the window, over the window's seconds
(host clock)."""


def read(ctx):
    if ctx["family"] == "sim" and ctx["window_s"] > 0:
        return ctx["requests"] / ctx["window_s"]
    return None
