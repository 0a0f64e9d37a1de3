"""Sectored simulator cells: one work unit is the cell's ``SweepGrid`` runs
on a geometry with ``sectors_per_line > 1``.

As ``bench.entries.sweep``, but every trace carries sector masks
(``bench.traffic.sim_traces_sectored``), and ``correct`` compares every
field of every point of every grid run in the window, the three sector
counters among them, with the plain sectored reference
(``bench.reference.sim_ref_sectored``). The traffic file's ``grid``
and ``traced_runs`` keys mean what they mean there.
"""
from __future__ import annotations

import math

import numpy as np

from bench.entries import sweep
from bench.reference import sim_ref_sectored
from bench.traffic import sim_traces_sectored

#: The simulator readers (``bench/metrics/sim.*``) read every entry of
#: this family: one that runs ``SweepGrid``s, with its spans.
FAMILY = "sim"

#: The sector counters, compared exactly like the rest.
SECTOR_COUNTERS = ("l1_sector_misses", "l2_sector_misses", "dram_sectors")
COUNTERS = sweep.COUNTERS + SECTOR_COUNTERS
FLOATS = sweep.FLOATS

compiled_hlo = sweep.compiled_hlo


def program_view(res) -> dict:
    """A program ``SimResult`` of a one-app trace as a flat dict."""
    return dict(sweep.program_view(res),
                **{k: getattr(res, k) for k in SECTOR_COUNTERS})


def reference_view(ref: dict, n_cores: int) -> dict:
    """A reference point (``sim_ref_sectored.simulate``) as the same
    flat dict."""
    return dict(sweep.reference_view(ref, n_cores),
                **{k: ref[k] for k in SECTOR_COUNTERS})


def gaps(got: dict, want: dict):
    """(largest counter gap in counts, largest relative timing gap), as
    ``bench.entries.sweep.gaps``, over the sector counters too."""
    counter_gap, float_gap = sweep.gaps(got, want)
    for k in SECTOR_COUNTERS:
        d = abs(got[k] - want[k])
        counter_gap = max(counter_gap, d if math.isfinite(d) else math.inf)
    return counter_gap, float_gap


def reference_points(config: dict, points, dtype=np.float64):
    """Reference view of every (arch, (addr, is_write, insn, sect))
    point, stacked by (arch, shape) like the program's buckets."""
    geom = config["geometry"]
    groups = {}
    for i, (arch, trace) in enumerate(points):
        groups.setdefault((arch, trace[0].shape), []).append(i)
    out = [None] * len(points)
    for (arch, _), idxs in groups.items():
        stack = lambda j: np.stack([points[i][1][j] for i in idxs])
        refs = sim_ref_sectored.simulate(
            geom, arch, stack(0), stack(1), stack(3),
            np.array([points[i][1][2] for i in idxs]), dtype=dtype)
        for i, r in zip(idxs, refs):
            out[i] = reference_view(r, geom["n_cores"])
    return out


class Cell:
    """One sectored simulator cell: traffic from the seed, its grids,
    its check."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 traced: bool = False):
        from repro.core import GpuGeometry, SweepGrid
        from repro.core.simulator import Trace
        self.config = config
        geom = GpuGeometry(**config["geometry"])
        traces = [tr for _, _, tr in sim_traces_sectored.traffic_traces(
            config, traffic, seed)]
        trace = lambda tr: Trace(tr[0], tr[1], tr[2], sect=tr[3])
        # arch slowest, trace fastest: the order SweepGrid enumerates
        self.points = [(a, tr) for a in traffic["archs"] for tr in traces]
        if traffic.get("grid", "stacked") == "per_point":
            self.grids = [SweepGrid([a], [geom], [trace(tr)])
                          for a, tr in self.points]
        else:
            self.grids = [SweepGrid(traffic["archs"], [geom],
                                    [trace(tr) for tr in traces])]
        if traced:
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
        """The unit's grid runs; results are on the host when it
        returns."""
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
