"""The benchmark's own copy of the simulator's trace generator.

A copy, not an import: the yardstick must not move when a later change
edits the program's generator (``repro.core.trace.generators``). At
seed 0 it reproduces the program's ``make_trace`` bit for bit (a test
pins that); any other seed draws other addresses with the same shapes,
so every seed simulates the same number of requests.

An app is a plain dict of the calibrated parameters (the configuration
file's ``apps`` table); a trace is ``(addr, is_write, insn_per_req)``:
``(rounds, cores, m)`` int32 line addresses, the same-shaped bool write
mask, and the amortized instructions per request.
"""
from __future__ import annotations

import zlib

import numpy as np

#: Disjoint address regions (line numbers) within one app's slice.
_SHARED_BASE = 0
_PRIVATE_BASE = 1 << 20
_STREAM_BASE = 1 << 26


def _stable_seed(*parts) -> int:
    return zlib.crc32("|".join(str(p) for p in parts).encode()) & 0x7FFFFFFF


def _jittered(app: dict, kernel: int) -> dict:
    """Deterministic per-kernel jitter around the app's parameters.

    Keyed by app and kernel only, never by the seed, so a kernel's
    working sets and intensity are the same in every run.
    """
    rng = np.random.default_rng(_stable_seed(app["name"], kernel))
    scale = lambda lo, hi: float(rng.uniform(lo, hi))
    return dict(
        app,
        shared_frac=float(np.clip(app["shared_frac"] * scale(0.6, 1.25),
                                  0, .95)),
        ws_shared=max(64, int(app["ws_shared"] * scale(0.5, 1.6))),
        ws_private=max(64, int(app["ws_private"] * scale(0.7, 1.3))),
        hot_frac=float(np.clip(app["hot_frac"] * scale(0.5, 1.5), 0, 0.8)),
        stream_frac=float(np.clip(app["stream_frac"] * scale(0.5, 1.8),
                                  0, .5)),
        insn_per_req=app["insn_per_req"] * scale(0.8, 1.25),
    )


def kernel_params(app: dict, kernel: int) -> dict:
    """Kernel 0 is the app's calibrated parameters; later kernels jitter."""
    if kernel < 0:
        raise ValueError(f"kernel must be >= 0, got {kernel}")
    return app if kernel == 0 else _jittered(app, kernel)


def make_trace(app: dict, *, n_cores: int, kernel: int = 0,
               seed: int = 0):
    """One kernel's request trace for all cores.

    Returns ``(addr, is_write, insn_per_req)``.
    """
    p = kernel_params(app, kernel)
    rng = np.random.default_rng(_stable_seed(app["name"], kernel, seed))
    T, C, m = p["rounds"], n_cores, p["m"]

    # per-(round, core) load classification
    u = rng.random((T, C))
    is_shared = u < p["shared_frac"]
    is_stream = (u >= p["shared_frac"]) & (
        u < p["shared_frac"] + p["stream_frac"])

    base = np.empty((T, C), np.int64)
    hot = rng.random((T, C)) < p["hot_frac"]
    shared_addr = np.where(
        hot,
        rng.integers(0, p["hot_size"], (T, C)),
        rng.integers(0, p["ws_shared"], (T, C)))
    base[is_shared] = (_SHARED_BASE + shared_addr)[is_shared]
    stream = (_STREAM_BASE + np.arange(C)[None, :] * (1 << 16)
              + np.cumsum(np.ones((T, C), np.int64), axis=0) * m)
    base[is_stream] = stream[is_stream]
    priv = (_PRIVATE_BASE + np.arange(C)[None, :] * (1 << 14)
            + rng.integers(0, p["ws_private"], (T, C)))
    rest = ~(is_shared | is_stream)
    base[rest] = priv[rest]

    # coalescing: consecutive lines, or independent re-samples
    coal = rng.random((T, C, 1)) < p["coalesced"]
    consec = base[:, :, None] + np.arange(m)[None, None, :]
    hot_s = rng.random((T, C, m)) < p["hot_frac"]
    resample_shared = _SHARED_BASE + np.where(
        hot_s,
        rng.integers(0, p["hot_size"], (T, C, m)),
        rng.integers(0, p["ws_shared"], (T, C, m)))
    resample_priv = (_PRIVATE_BASE + np.arange(C)[None, :, None] * (1 << 14)
                     + rng.integers(0, p["ws_private"], (T, C, m)))
    scattered = np.where(is_shared[:, :, None], resample_shared,
                         resample_priv)
    scattered = np.where(is_stream[:, :, None], consec, scattered)
    addr = np.where(coal, consec, scattered).astype(np.int64)
    if addr.min() < 0 or addr.max() > np.iinfo(np.int32).max:
        raise ValueError(f"trace addresses span [{addr.min()}, "
                         f"{addr.max()}], outside int32")

    is_write = rng.random((T, C, m)) < p["write_frac"]
    return addr.astype(np.int32), is_write, float(p["insn_per_req"])


def app_table(config: dict) -> dict:
    """{app name: full parameter dict} from a configuration file."""
    defaults = config["app_defaults"]
    return {name: dict(defaults, name=name, **params)
            for name, params in config["apps"].items()}


def traffic_traces(config: dict, traffic: dict, seed: int):
    """[(app, kernel, trace)] for every kernel a sweep traffic file names."""
    apps = app_table(config)
    n_cores = config["geometry"]["n_cores"]
    out = []
    for name in traffic["apps"]:
        for k in range(apps[name]["n_kernels"]):
            out.append((name, k, make_trace(apps[name], n_cores=n_cores,
                                            kernel=k, seed=seed)))
    return out
