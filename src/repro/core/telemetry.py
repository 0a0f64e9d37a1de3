"""Opt-in telemetry: counter registry, windows, exact histograms.

Both scan hot paths (``repro.core.simulator`` and
``repro.serving.engine``) fold every per-round signal — hits, remote
probes, per-app latency sums, NoC queue depth, link flits — into their
carries and keep only end-of-run totals. This module is the shared
vocabulary for *keeping* the time axis:

* :class:`TelemetryConfig` — a frozen, hashable config passed as a
  **static** ``telemetry=`` argument to ``simulate`` /
  ``SweepGrid.run`` / ``serve_stream``. ``None`` (the default) keeps
  the existing executables byte-identical — the telemetry branch is
  never traced, so goldens and compile caches are untouched (tier-1
  asserted). A config makes the scans additionally emit per-*window*
  cumulative counter snapshots (window-strided: memory is
  ``rounds/window x counters``, never ``rounds x counters``).
* :class:`Counter` + :data:`SIM_COUNTERS` / :data:`SERVE_COUNTERS` —
  the declarative registry naming every emitted counter (unit, axis,
  description) and mapping it onto the carry/emission field it already
  rides in. Exporters (``repro.obs``) iterate the registry instead of
  hard-coding field names.
* Exact latency histograms — int32 bincount counters in the carries.
  The serving engine's cost model is integral by default, so its
  histogram is value-resolved (one bucket per modeled cycle) and
  quantiles reconstruct ``np.percentile`` **exactly**
  (:func:`hist_quantile` replicates numpy's linear interpolation bit
  for bit); the simulator's L1-complete latencies are fractional, so
  its histogram is log-2-bucketed (:func:`log2_bucket`) and quantile
  reads are exact at bucket granularity (:func:`hist_quantile_edges`
  returns the conservative upper edge).

The window contract: ``rounds % window == 0`` (checked with a
divisor-suggesting error). Snapshots are *cumulative*, so the final
snapshot equals the run total by construction and per-window deltas
telescope back to it exactly — every f32 counter value is exactly
representable in f64, consecutive-snapshot differences are exact, and
their f64 sum reproduces ``total - 0`` with no rounding (the
conservation guarantee ``repro.obs.timeline`` checks).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import numpy as np

__all__ = [
    "TelemetryConfig", "Counter", "SIM_COUNTERS", "SERVE_COUNTERS",
    "log2_bucket", "log2_edges", "hist_quantile", "hist_quantile_edges",
    "serving_hist_bins",
]


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """Static telemetry knob (hashable: part of the executable key).

    ``window`` is the snapshot stride in *rounds* (simulator) or
    *admission rounds* (serving engine). ``histograms`` adds the
    latency-histogram counter to the carry; ``sim_hist_bins`` sizes the
    simulator's log-2 bucket array (bucket ``i`` covers
    ``[2^i, 2^(i+1))`` cycles, bucket 0 also absorbs sub-cycle
    latencies, the last bucket absorbs overflow).
    """
    window: int = 32
    histograms: bool = True
    sim_hist_bins: int = 32

    def __post_init__(self):
        if self.window < 1:
            raise ValueError(f"window must be >= 1, got {self.window}")
        if self.sim_hist_bins < 2:
            raise ValueError(
                f"sim_hist_bins must be >= 2, got {self.sim_hist_bins}")

    def window_for(self, rounds: int) -> int:
        """Validate the window against a run length and return it."""
        if rounds % self.window:
            divisors = [d for d in range(1, rounds + 1)
                        if rounds % d == 0]
            near = min(divisors, key=lambda d: abs(d - self.window))
            raise ValueError(
                f"telemetry window {self.window} must divide the run "
                f"length {rounds} (nearest divisor: {near})")
        return self.window


@dataclasses.dataclass(frozen=True)
class Counter:
    """One registered telemetry counter.

    ``field`` names the carry/emission field the counter maps onto —
    ``"noc.<key>"`` reaches into the carried NoC state dict. ``axis``
    is the trailing shape semantic: ``scalar`` (0-d), ``core`` /
    ``app`` / ``link`` (simulator), ``shard`` / ``tenant`` (serving),
    ``bucket`` (histograms). ``cumulative`` counters snapshot a
    monotone running sum (per-window series are deltas); gauges
    (``cumulative=False``) snapshot an instantaneous value (per-window
    series are samples).
    """
    name: str
    unit: str
    axis: str
    field: str
    description: str
    cumulative: bool = True


#: Simulator counters, mapped onto the ``lax.scan`` carry of
#: ``repro.core.simulator._round`` (the ``stats`` dict + carried NoC
#: state). Window snapshots expose exactly these.
SIM_COUNTERS: Tuple[Counter, ...] = (
    Counter("cycles", "cycles", "core", "cycles",
            "per-core accumulated round cost (completion clock)"),
    Counter("requests", "requests", "scalar", "requests",
            "memory requests issued"),
    Counter("local_hits", "requests", "scalar", "local_hits",
            "requests served by the issuing core's own L1"),
    Counter("remote_hits", "requests", "scalar", "remote_hits",
            "requests served by a peer L1 in the cluster"),
    Counter("l2_accesses", "requests", "scalar", "l2_accesses",
            "requests escalated to the shared L2"),
    Counter("dram", "requests", "scalar", "dram",
            "L2 misses that went to DRAM"),
    Counter("noc_flits", "flits", "scalar", "noc_flits",
            "interconnect flits injected by the L1 complex"),
    Counter("l1_lat_sum", "cycles", "scalar", "l1_lat_sum",
            "sum of L1-complex completion times over served loads"),
    Counter("l1_lat_n", "loads", "scalar", "l1_lat_n",
            "loads fully served inside the L1 complex"),
    Counter("app_local", "requests", "app", "app_local",
            "per-app local L1 hits (mix attribution)"),
    Counter("app_remote", "requests", "app", "app_remote",
            "per-app remote L1 hits (mix attribution)"),
    Counter("app_lat_sum", "cycles", "app", "app_lat_sum",
            "per-app L1-complete latency sum"),
    Counter("app_lat_n", "loads", "app", "app_lat_n",
            "per-app loads fully served in the L1 complex"),
    Counter("noc.injected", "flits", "scalar", "noc.injected",
            "flits injected into the interconnect model"),
    Counter("noc.delivered", "flits", "scalar", "noc.delivered",
            "flits delivered by the interconnect model"),
    Counter("noc.delay_sum", "cycles", "scalar", "noc.delay_sum",
            "summed NoC queueing delay over crossing requests"),
    Counter("noc.delay_n", "requests", "scalar", "noc.delay_n",
            "requests that crossed the interconnect"),
    Counter("noc.link_flits", "flits", "link", "noc.link_flits",
            "per-link flits carried"),
    Counter("noc.link_busy", "cycles", "link", "noc.link_busy",
            "per-link busy cycles"),
    Counter("noc.queue", "flits", "link", "noc.queue",
            "per-port queue depth at window end (backpressure gauge)",
            cumulative=False),
    Counter("lat_hist", "loads", "bucket", "lat_hist",
            "log2-bucketed L1-complete latency histogram"),
    # sectored geometries only (sectors_per_line > 1)
    Counter("l1_sector_misses", "requests", "scalar", "l1_sector_misses",
            "own-L1 tag hits lacking a requested sector"),
    Counter("l2_sector_misses", "requests", "scalar", "l2_sector_misses",
            "L2 tag hits lacking a needed sector"),
    Counter("dram_sectors", "sectors", "scalar", "dram_sectors",
            "sectors fetched from DRAM"),
)

#: Serving-engine counters, derived from the per-sub-round emission
#: grids ``serve_stream`` already streams to the host (plus the
#: device-side latency bincount).
SERVE_COUNTERS: Tuple[Counter, ...] = (
    Counter("admitted", "requests", "shard", "admitted",
            "requests admitted (valid slots) per shard"),
    Counter("local_hits", "blocks", "shard", "nl",
            "prefix blocks reused from the local pool"),
    Counter("remote_hits", "blocks", "shard", "nr",
            "prefix blocks fetched from a peer shard"),
    Counter("recomputed", "blocks", "shard", "nc",
            "prefix blocks recomputed (prefill)"),
    Counter("latency_sum", "cycles", "shard", "lat",
            "summed modeled request latency per shard"),
    Counter("cycles", "cycles", "scalar", "cycles",
            "summed per-admission-round critical paths"),
    Counter("probe_messages", "messages", "scalar", "pm",
            "broadcast directory probes sent"),
    Counter("tenant_requests", "requests", "tenant", "tenant_requests",
            "requests admitted per tenant"),
    Counter("tenant_blocks", "blocks", "tenant", "tenant_blocks",
            "prefix blocks walked per tenant"),
    Counter("lat_hist", "requests", "bucket", "lat_hist",
            "value-resolved modeled-latency histogram (1 cycle/bucket)"),
)


# ---------------------------------------------------------------------------
# histogram helpers
# ---------------------------------------------------------------------------

def log2_bucket(x, bins: int):
    """Device-side log2 bucket index of positive latencies (jnp).

    Bucket ``i`` covers ``[2^i, 2^(i+1))``; values below 1 land in
    bucket 0 and values at or above ``2^(bins-1)`` clip into the last
    bucket. Powers of two are exact in float32, so bucket edges are
    crisp.
    """
    import jax.numpy as jnp
    b = jnp.floor(jnp.log2(jnp.maximum(x, 1.0)))
    return jnp.clip(b, 0, bins - 1).astype(jnp.int32)


def log2_edges(bins: int) -> np.ndarray:
    """(bins,) float64 upper edges of the log2 buckets (2^(i+1))."""
    return 2.0 ** (np.arange(bins, dtype=np.float64) + 1.0)


def serving_hist_bins(max_lat: float) -> int:
    """Bucket count for a value-resolved serving histogram.

    One bucket per modeled cycle up to the engine's per-request latency
    bound (``_check_headroom``'s ``max_lat``), plus an overflow bucket
    for non-ideal NoC delay beyond the base-cost bound.
    """
    return int(math.ceil(max_lat)) + 2


def _np_lerp(a: float, b: float, t: float) -> float:
    """numpy's percentile interpolation, replicated bit for bit."""
    diff = b - a
    if t >= 0.5:
        return b - diff * (1.0 - t)
    return a + diff * t


def hist_quantile(counts, q: float) -> float:
    """Exact ``np.percentile(values, q)`` from a value-resolved histogram.

    ``counts[v]`` is the number of observations with value exactly
    ``v`` (the serving engine's integral cost model quantized at one
    modeled cycle per bucket). Reconstructs numpy's default linear
    interpolation between order statistics, including its asymmetric
    lerp, so the result is bit-identical to materializing the array.
    """
    counts = np.asarray(counts, np.int64)
    n = int(counts.sum())
    if n == 0:
        return 0.0
    pos = (q / 100.0) * (n - 1)
    i = int(np.floor(pos))
    t = pos - i
    cum = np.cumsum(counts)
    lo = int(np.searchsorted(cum, i, side="right"))
    if t == 0.0:
        return float(lo)
    hi = int(np.searchsorted(cum, i + 1, side="right"))
    return _np_lerp(float(lo), float(hi), t)


def hist_quantile_edges(counts, q: float,
                        edges: Optional[np.ndarray] = None) -> float:
    """Conservative quantile from a bucketed histogram (upper edge).

    For log2-bucketed histograms the order statistic's bucket is exact
    but the value inside it is not; return the bucket's upper edge so
    the reported pXX is a guaranteed upper bound. ``edges`` defaults to
    the log2 edges sized to ``counts``.
    """
    counts = np.asarray(counts, np.int64)
    n = int(counts.sum())
    if n == 0:
        return 0.0
    if edges is None:
        edges = log2_edges(counts.size)
    # order statistic at ceil(q/100 * (n-1)): the conservative side
    pos = int(math.ceil((q / 100.0) * (n - 1)))
    cum = np.cumsum(counts)
    b = int(np.searchsorted(cum, pos, side="right"))
    return float(edges[min(b, counts.size - 1)])
