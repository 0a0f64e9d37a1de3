"""Sectored traffic for the simulator cells of a sectored geometry.

Each trace is :func:`bench.traffic.sim_traces.make_trace` of an app
whose working sets (``ws_shared``, ``ws_private``) are scaled by the
traffic file's ``ws_scale`` (the apps were sized against a 512-line L1,
so a larger L1 needs larger working sets for its cluster to share
anything), with a sector mask per request drawn from the seed on a
stream of its own:

* the requests of a coalesced load, and of a stream load, ask for the
  whole line;
* a scattered re-sample (shared or private) asks for one sector, drawn
  uniformly.

A trace is ``(addr, is_write, insn_per_req, sect)``, ``sect`` the
``(rounds, cores, m)`` uint8 masks. The seed moves addresses, writes,
jitter and masks; the shapes stay.
"""
from __future__ import annotations

import numpy as np

from bench.traffic import sim_traces


def scaled_app(app: dict, scale: int) -> dict:
    """The app with both working sets ``scale`` times larger."""
    return dict(app, ws_shared=app["ws_shared"] * scale,
                ws_private=app["ws_private"] * scale)


def whole_line_loads(app: dict, *, n_cores: int, kernel: int, seed: int):
    """(rounds, cores) bool: the loads ``make_trace`` coalesces or
    streams, read by repeating its draws up to the coalescing one."""
    p = sim_traces.kernel_params(app, kernel)
    rng = np.random.default_rng(sim_traces._stable_seed(app["name"], kernel,
                                                        seed))
    T, C = p["rounds"], n_cores
    u = rng.random((T, C))
    is_stream = (u >= p["shared_frac"]) & (
        u < p["shared_frac"] + p["stream_frac"])
    rng.random((T, C))                          # hot
    rng.integers(0, p["hot_size"], (T, C))      # hot shared line
    rng.integers(0, p["ws_shared"], (T, C))     # shared line
    rng.integers(0, p["ws_private"], (T, C))    # private line
    coal = rng.random((T, C)) < p["coalesced"]
    return coal | is_stream


def make_trace(app: dict, *, n_cores: int, kernel: int, seed: int,
               sectors: int):
    """One kernel's sectored trace: ``(addr, is_write, insn, sect)``."""
    addr, is_write, insn = sim_traces.make_trace(app, n_cores=n_cores,
                                                 kernel=kernel, seed=seed)
    whole = whole_line_loads(app, n_cores=n_cores, kernel=kernel, seed=seed)
    m = addr.shape[2]
    consecutive = addr == addr[:, :, :1] + np.arange(m, dtype=np.int32)
    if not consecutive[whole].all():
        raise RuntimeError("whole_line_loads no longer repeats "
                           "make_trace's draws")
    rng = np.random.default_rng(sim_traces._stable_seed(
        app["name"], kernel, seed, "sectors"))
    one = np.left_shift(1, rng.integers(0, sectors, addr.shape))
    sect = np.where(whole[:, :, None], (1 << sectors) - 1, one)
    return addr, is_write, insn, sect.astype(np.uint8)


def traffic_traces(config: dict, traffic: dict, seed: int):
    """[(app, kernel, trace)] for the kernels the traffic file names
    (every kernel where it names none) of each of its apps."""
    apps = sim_traces.app_table(config)
    geom = config["geometry"]
    out = []
    for name in traffic["apps"]:
        app = scaled_app(apps[name], traffic.get("ws_scale", 1))
        for k in traffic.get("kernels", range(app["n_kernels"])):
            out.append((name, k, make_trace(
                app, n_cores=geom["n_cores"], kernel=k, seed=seed,
                sectors=geom["sectors_per_line"])))
    return out
