"""Simulator cells: one work unit is one ``SweepGrid.run`` of the cell's grid.

The grid is every kernel of the traffic file's apps under each of its
architectures, on the configuration's geometry, with every other
``SweepGrid`` default left alone (``lax`` probe, ``ideal`` NoC, all
local devices). A traffic file with ``"grid": "per_point"`` runs the
same points as one single-point grid each, back to back: no bucket
stacks two points under ``vmap``. A traced run's unit holds the first
``"traced_runs"`` grid runs (all where the file names none): the
profiler keeps a fixed number of events, and the traced window has to
hold every event of the runs it divides by. ``correct`` compares every field of every point of
every grid run in the window with the plain reference
(``bench.reference.sim_ref``).
"""
from __future__ import annotations

import math

import numpy as np

from bench.reference import sim_ref
from bench.traffic import sim_traces

#: The simulator readers (``bench/metrics/sim.*``) read every entry of
#: this family: one that runs ``SweepGrid``s, with its spans.
FAMILY = "sim"

#: Counters: integers, compared exactly (gap in counts).
COUNTERS = ("local", "remote", "requests", "lat_n", "lat_sum", "l2", "dram",
            "noc_flits", "injected", "delivered", "queued", "queue_delay",
            "max_link_util", "mean_link_util", "instructions", "cores",
            "local_rate", "remote_rate", "l1_rate")
#: Timing sums in the configuration's float32: compared by relative gap.
FLOATS = ("cycles", "app_cycles", "ipc", "l1_latency")


def program_view(res) -> dict:
    """A program ``SimResult`` of a one-app trace as the flat dict the
    comparison reads."""
    (app,), noc = res.per_app, res.noc
    n = app.requests
    return dict(
        local=app.local_hits, remote=app.remote_hits, requests=n,
        lat_n=app.l1_lat_n, lat_sum=app.l1_lat_sum, l2=res.l2_accesses,
        dram=res.dram_accesses, noc_flits=res.noc_flits,
        injected=noc.flits_injected, delivered=noc.flits_delivered,
        queued=noc.flits_queued, queue_delay=noc.mean_queue_delay,
        max_link_util=noc.max_link_util, mean_link_util=noc.mean_link_util,
        instructions=res.instructions, cores=app.cores,
        local_rate=res.local_hit_rate * n, remote_rate=res.remote_hit_rate * n,
        l1_rate=res.l1_hit_rate * n, cycles=res.cycles, app_cycles=app.cycles,
        ipc=res.ipc, l1_latency=res.l1_latency)


def reference_view(ref: dict, n_cores: int) -> dict:
    """A reference point (``sim_ref.simulate``) as the same flat dict."""
    n = ref["requests"]
    cycles = float(np.max(ref["cycles_per_core"]))
    return dict(
        local=ref["local"], remote=ref["remote"], requests=n,
        lat_n=ref["lat_n"], lat_sum=ref["lat_sum"], l2=ref["l2"],
        dram=ref["dram"], noc_flits=ref["noc_flits"],
        injected=ref["injected"], delivered=ref["injected"], queued=0,
        queue_delay=0, max_link_util=0, mean_link_util=0,
        instructions=ref["instructions"], cores=n_cores,
        # rates go through the same float division as the program's
        local_rate=ref["local"] / n * n, remote_rate=ref["remote"] / n * n,
        l1_rate=(ref["local"] + ref["remote"]) / n * n, cycles=cycles,
        app_cycles=cycles, ipc=ref["instructions"] / cycles,
        l1_latency=(ref["lat_sum"] / ref["lat_n"] if ref["lat_n"]
                    else math.nan))


def gaps(got: dict, want: dict):
    """(largest counter gap in counts, largest relative timing gap).
    Two NaNs agree (no load fully served in the L1 complex); any other
    missing or non-finite reading is an infinite gap."""
    def gap(a, b, rel):
        if math.isnan(a) and math.isnan(b):
            return 0.0
        d = abs(a - b)
        if rel:
            d = d / abs(b) if b else d
        return d if math.isfinite(d) else math.inf
    return (max(gap(got[k], want[k], False) for k in COUNTERS),
            max(gap(got[k], want[k], True) for k in FLOATS))


def reference_points(config: dict, points, dtype=np.float64):
    """Reference view of every (arch, (addr, is_write, insn)) point,
    stacked by (arch, shape) like the program's buckets."""
    geom = config["geometry"]
    groups = {}
    for i, (arch, trace) in enumerate(points):
        groups.setdefault((arch, trace[0].shape), []).append(i)
    out = [None] * len(points)
    for (arch, _), idxs in groups.items():
        refs = sim_ref.simulate(
            geom, arch, np.stack([points[i][1][0] for i in idxs]),
            np.stack([points[i][1][1] for i in idxs]),
            np.array([points[i][1][2] for i in idxs]), dtype=dtype)
        for i, r in zip(idxs, refs):
            out[i] = reference_view(r, geom["n_cores"])
    return out


def compiled_hlo():
    """(module name, optimised HLO text) of every executable the sweep
    dispatched, which ``bench/stages.py`` matches the trace against."""
    from repro.core import sweep
    return sweep.compiled_hlo()


class Cell:
    """One simulator cell: traffic from the seed, its grid, its check."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 traced: bool = False):
        from repro.core import GpuGeometry, SweepGrid
        from repro.core.simulator import Trace
        self.config = config
        traces = [tr for _, _, tr in
                  sim_traces.traffic_traces(config, traffic, seed)]
        # arch slowest, trace fastest: the order SweepGrid enumerates
        self.points = [(a, tr) for a in traffic["archs"] for tr in traces]
        geom = GpuGeometry(**config["geometry"])
        if traffic.get("grid", "stacked") == "per_point":
            self.grids = [SweepGrid([a], [geom], [Trace(*tr)])
                          for a, tr in self.points]
        else:
            self.grids = [SweepGrid(traffic["archs"], [geom],
                                    [Trace(*tr) for tr in traces])]
        if traced:
            # the first ``traced_runs`` grid runs, which the profiler
            # keeps whole; their points are the first points
            self.grids = self.grids[:traffic.get("traced_runs")]
            self.points = self.points[:sum(len(g.points)
                                           for g in self.grids)]
        #: requests of each ``SweepGrid.run`` of a unit, in order
        self.run_requests = [sum(p.trace.addr.size for p in g.points)
                             for g in self.grids]
        self.requests_per_unit = sum(self.run_requests)
        self._want = None

    def reference(self):
        """The reference view of every point (computed once)."""
        if self._want is None:
            self._want = reference_points(self.config, self.points)
        return self._want

    def unit(self):
        """One grid run (or one per point); results are on the host when
        it returns."""
        results = []
        for grid in self.grids:
            results += grid.run().results
        return results

    def views(self, results):
        """Program views of one unit's results; None if it never answered
        or points are missing."""
        if results is None or len(results) != len(self.points):
            return None
        return [program_view(r) for r in results]

    def control_views(self):
        """The control: the reference itself, its timing in bfloat16 (the
        precision below the configuration's float32)."""
        import ml_dtypes
        return reference_points(self.config, self.points,
                                dtype=ml_dtypes.bfloat16)

    def check(self, outputs, limits: dict):
        """Compare every point of every unit with the reference."""
        return self.check_views([self.views(r) for r in outputs], limits)

    def check_views(self, unit_views, limits: dict):
        """({name: (reading, limit)}, failed requests) over units' views."""
        want = self.reference()
        worst_c = worst_f = 0.0
        failed = 0
        for got in unit_views:
            if got is None:
                worst_c = math.inf
                failed += self.requests_per_unit
                continue
            for (arch, tr), g, w in zip(self.points, got, want):
                c, f = gaps(g, w)
                worst_c, worst_f = max(worst_c, c), max(worst_f, f)
                if c > limits["counter_gap"] or f > limits["float_rel_gap"]:
                    failed += tr[0].size
        return ({"counter_gap": (worst_c, limits["counter_gap"]),
                 "float_rel_gap": (worst_f, limits["float_rel_gap"])},
                failed)
