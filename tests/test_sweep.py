"""SweepGrid engine tests: golden equivalence vs per-point ``simulate``,
executable accounting (policy stacking + scalar-geometry batching),
device sharding (subprocess, 8 forced host devices), and the NaN metric
guards in ``repro.core.metrics``."""
import dataclasses
import math
import os
import subprocess
import sys
import textwrap

import pytest

from repro.core import (APPS, PAPER_GEOMETRY, SimResult, SweepGrid,
                        geomean, make_trace, run_suite, simulate)
from repro.core.arch import PAPER_ARCHITECTURES
from repro.core.metrics import AppResult

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _traces(app, rounds=96, kernels=2):
    p = dataclasses.replace(APPS[app], rounds=rounds)
    return [make_trace(p, kernel=k) for k in range(kernels)]


def same_result(a: SimResult, b: SimResult) -> bool:
    """Bit-exact equality that treats identical NaNs as equal.

    ``SimResult.l1_latency`` is documented to be NaN when no load was
    ever fully served inside the L1 complex; grid and per-point paths
    must agree on that too.
    """
    return all(x == y or (x != x and y != y)
               for x, y in zip(tuple(a), tuple(b)))


# ---------------------------------------------------------------------------
# golden equivalence: grid == sequential simulate, bit for bit
# ---------------------------------------------------------------------------
def test_sweep_grid_bit_identical_to_simulate_all_paper_archs():
    traces = _traces("cfd")
    geoms = [PAPER_GEOMETRY, dataclasses.replace(PAPER_GEOMETRY, svc_l2=8)]
    grid = SweepGrid(PAPER_ARCHITECTURES, geoms, traces)
    run = grid.run()
    assert len(run.results) == len(grid.points)
    for pt, r in zip(grid.points, run.results):
        assert same_result(r, simulate(pt.arch, pt.trace, pt.geom)), \
            (pt.arch, pt.geom.svc_l2)


def test_sweep_grid_bit_identical_for_stacked_ata_family():
    """ata/ata_fifo/ata_bypass share one switch-selected executable; each
    variant must still match its own per-point simulate() exactly."""
    # long enough that L1 sets fill and the replacement policies diverge
    # — otherwise a policy_idx that silently selected branch 0 for every
    # point would still pass the equality checks below.
    traces = _traces("cfd", rounds=768, kernels=1)
    grid = SweepGrid(("ata", "ata_fifo", "ata_bypass"), None, traces)
    run = grid.run()
    assert run.report.n_executables == 1
    for pt, r in zip(grid.points, run.results):
        assert same_result(r, simulate(pt.arch, pt.trace))
    by_arch = {pt.arch: r for pt, r in zip(grid.points, run.results)}
    assert tuple(by_arch["ata"]) != tuple(by_arch["ata_fifo"])


# ---------------------------------------------------------------------------
# executable accounting
# ---------------------------------------------------------------------------
def test_scalar_geometries_share_one_executable_per_group():
    """2 dataflow groups x 3 scalar-only geometries x kernels -> exactly
    2 executables (the acceptance-criteria grid, unsharded here)."""
    traces = _traces("doitgen", kernels=3)
    geoms = [PAPER_GEOMETRY,
             dataclasses.replace(PAPER_GEOMETRY, svc_port=4),
             dataclasses.replace(PAPER_GEOMETRY, lat_l2=240)]
    grid = SweepGrid(("private", "ata"), geoms, traces)
    run = grid.run()
    assert run.report.n_points == 2 * 3 * 3
    assert run.report.n_executables == 2, run.report
    # warm second run: same executables, zero fresh compiles
    rerun = SweepGrid(("private", "ata"), geoms, traces).run()
    assert rerun.report.n_compiles == 0
    for a, b in zip(run.results, rerun.results):
        assert tuple(a) == tuple(b)


def test_structural_geometries_group_per_shape():
    traces = _traces("cfd", kernels=1)
    geoms = [PAPER_GEOMETRY,
             dataclasses.replace(PAPER_GEOMETRY, l1_sets=16)]
    run = SweepGrid(("ata",), geoms, traces).run()
    assert run.report.n_executables == 2   # one per structure
    for pt, r in zip(SweepGrid(("ata",), geoms, traces).points,
                     run.results):
        assert same_result(r, simulate(pt.arch, pt.trace, pt.geom))


def test_sweep_grid_validates_archs_and_geometry():
    tr = _traces("cfd", kernels=1)
    with pytest.raises(ValueError, match="arch must be one of"):
        SweepGrid(("no_such_arch",), None, tr)
    with pytest.raises(ValueError, match="must divide"):
        SweepGrid(("ata",),
                  [dataclasses.replace(PAPER_GEOMETRY, cluster_size=7)], tr)


# ---------------------------------------------------------------------------
# suite driver rides the grid
# ---------------------------------------------------------------------------
def test_run_suite_matches_per_point_simulate():
    suite = run_suite(apps=("cfd", "HS3D"), archs=("private", "ata"),
                      kernels_per_app=2, rounds=96)
    for app in ("cfd", "HS3D"):
        traces = [make_trace(dataclasses.replace(APPS[app], rounds=96),
                             kernel=k) for k in range(2)]
        for arch in ("private", "ata"):
            got = suite[app][arch].per_kernel
            assert len(got) == 2
            for tr, r in zip(traces, got):
                assert same_result(r, simulate(arch, tr))


# ---------------------------------------------------------------------------
# device sharding (subprocess: forced 8-device host platform)
# ---------------------------------------------------------------------------
def test_sharded_sweep_on_8_devices_bit_identical():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent("""
        import dataclasses, jax
        from repro.core import (APPS, PAPER_GEOMETRY, SweepGrid, make_trace,
                                simulate)
        assert len(jax.devices()) == 8
        p = dataclasses.replace(APPS["cfd"], rounds=64)
        traces = [make_trace(p, kernel=k) for k in range(3)]
        geoms = [PAPER_GEOMETRY,
                 dataclasses.replace(PAPER_GEOMETRY, svc_port=4),
                 dataclasses.replace(PAPER_GEOMETRY, lat_dram=400)]
        grid = SweepGrid(("private", "ata"), geoms, traces)
        run = grid.run()
        assert run.report.n_devices == 8, run.report
        assert run.report.n_executables == 2, run.report
        same = lambda a, b: all(x == y or (x != x and y != y)
                                for x, y in zip(tuple(a), tuple(b)))
        for pt, r in zip(grid.points, run.results):
            assert same(r, simulate(pt.arch, pt.trace, pt.geom))
        print("SHARDED_SWEEP_OK", run.report.n_points)
    """)], capture_output=True, text=True, env=env, timeout=900)
    assert "SHARDED_SWEEP_OK" in r.stdout, r.stdout + r.stderr


def test_sweep_run_rejects_more_devices_than_present():
    """A requested device count is never clamped to what exists."""
    import jax
    grid = SweepGrid(("ata",), None, _traces("cfd", kernels=1))
    with pytest.raises(ValueError, match="n_devices"):
        grid.run(n_devices=len(jax.devices()) + 1)
    with pytest.raises(ValueError, match="n_devices"):
        grid.run(n_devices=0)


# ---------------------------------------------------------------------------
# NaN metric guards
# ---------------------------------------------------------------------------
def _res(l1_latency, l1_hit_rate=0.5):
    return SimResult(ipc=1.0, l1_latency=l1_latency,
                     local_hit_rate=0.4, remote_hit_rate=0.1,
                     l1_hit_rate=l1_hit_rate, l2_accesses=10.0,
                     dram_accesses=5.0, noc_flits=20.0, cycles=100.0,
                     instructions=100.0)


def test_app_result_latency_ignores_all_streaming_kernel_nan():
    app = AppResult("x", "ata", [_res(30.0), _res(float("nan")),
                                 _res(50.0)])
    assert app.l1_latency == pytest.approx(40.0)
    assert app.l1_hit_rate == pytest.approx(0.5)
    all_nan = AppResult("x", "ata", [_res(float("nan"))])
    assert math.isnan(all_nan.l1_latency)


def test_geomean_rejects_nan_and_nonpositive():
    assert geomean([1.0, 4.0]) == pytest.approx(2.0)
    with pytest.raises(ValueError, match="finite positive"):
        geomean([1.0, float("nan")])
    with pytest.raises(ValueError, match="finite positive"):
        geomean([1.0, 0.0])
    with pytest.raises(ValueError, match="finite positive"):
        geomean([1.0, -2.0])
    with pytest.raises(ValueError, match="empty"):
        geomean([])
