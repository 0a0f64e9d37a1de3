"""Probe-backend exact equivalence: lax vs lax_unfused vs
pallas_interpret.

The probe backend (``repro.core.probe``) is a *static* axis of the
simulator — every backend lowers a structurally different program but
must return bit-identical integers/booleans, so every committed golden
is backend-invariant. These tests pin that at three levels: the fused
op itself, a full ``l1_stage`` (outputs *and* post-touch tag state),
and end-to-end ``SimResult`` equality (solo, mix, and non-ideal NoC),
plus the ``SweepGrid`` axis semantics (per-backend executables,
identical results).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (APPS, PAPER_GEOMETRY, SweepGrid, SweepPoint,
                        WorkloadMix, make_trace, simulate)
from repro.core import tagarray
from repro.core.arch import get_arch
from repro.core.geometry import GpuGeometry
from repro.core.probe import (DEFAULT_PROBE_BACKEND, PROBE_BACKENDS,
                              check_probe_backend, fused_probe_rank)
from repro.core.simulator import _l1_state, _request_batch

RNG = np.random.default_rng(7)

#: backends runnable on CPU — "pallas" (Mosaic-compiled) needs a TPU.
CPU_BACKENDS = ("lax", "lax_unfused", "pallas_interpret")

SMALL = dataclasses.replace(PAPER_GEOMETRY, n_cores=6, cluster_size=3,
                            l1_sets=4, l1_ways=8)


def _warmed_state(geom: GpuGeometry, policy=None, fill_frac=0.6, seed=0):
    """A tag state with random valid/dirty lines (set-aligned tags)."""
    rng = np.random.default_rng(seed)
    C, S, W = geom.n_cores, geom.l1_sets, geom.l1_ways
    st = (_l1_state(geom, [policy]) if policy is not None
          else tagarray.init_tag_state(C, S, W))
    tags = rng.integers(0, 64, (C, S, W))
    valid = rng.random((C, S, W)) < fill_frac
    dirty = valid & (rng.random((C, S, W)) < 0.2)
    return dict(st, tags=jnp.asarray(tags * S + np.arange(S)[None, :, None],
                                     jnp.int32),
                valid=jnp.asarray(valid),
                dirty=jnp.asarray(dirty))


def _random_reqs(geom: GpuGeometry, m=4, seed=1):
    rng = np.random.default_rng(seed)
    C = geom.n_cores
    addr = jnp.asarray(rng.integers(0, 64 * geom.l1_sets, (C, m)),
                       jnp.int32)
    is_write = jnp.asarray(rng.random((C, m)) < 0.25)
    return _request_batch(geom, addr, is_write)


def _tree_equal(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# op level
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("geom,m", [(SMALL, 4), (PAPER_GEOMETRY, 2),
                                    (PAPER_GEOMETRY, 5)],
                         ids=["small", "paper", "padded"])
@pytest.mark.parametrize("backend",
                         [b for b in CPU_BACKENDS if b != "lax"])
def test_fused_probe_rank_backends_bitexact(geom, m, backend):
    # m=5 -> R=150, not a multiple of the kernel's BR=128: exercises
    # the dead-lane padding path of the pallas wrapper.
    l1 = _warmed_state(geom)
    reqs = _random_reqs(geom, m=m)
    pre = jnp.asarray(RNG.random(reqs.addr.shape[0]) < 0.1)
    for pre_served in (None, pre):
        ref = fused_probe_rank(geom, l1, reqs, pre_served=pre_served,
                               backend="lax")
        got = fused_probe_rank(geom, l1, reqs, pre_served=pre_served,
                               backend=backend)
        lh = np.asarray(ref.local_hit)
        assert lh.any(), "warmed state should produce some local hits"
        np.testing.assert_array_equal(np.asarray(got.local_hit), lh)
        # touch_way is only consumed (and only defined) where local_hit
        np.testing.assert_array_equal(
            np.where(lh, np.asarray(got.touch_way), 0),
            np.where(lh, np.asarray(ref.touch_way), 0))
        np.testing.assert_array_equal(np.asarray(got.remote_ok),
                                      np.asarray(ref.remote_ok))
        rok = np.asarray(ref.remote_ok)
        assert rok.any(), "warmed state should produce remote hits"
        for field in ("src_cache", "prank", "psize"):
            np.testing.assert_array_equal(
                np.where(rok, np.asarray(getattr(got, field)), 0),
                np.where(rok, np.asarray(getattr(ref, field)), 0))


# ---------------------------------------------------------------------------
# stage level: outputs AND the post-touch tag state
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["ata", "ata_fifo", "ata_bypass",
                                  "victim"])
def test_l1_stage_bitexact_across_backends(arch):
    policy = get_arch(arch)
    geom = SMALL
    l1 = _warmed_state(geom, policy=policy)
    reqs = _random_reqs(geom, seed=3)
    ref = policy.l1_stage(geom, l1, reqs, jnp.int32(5), backend="lax")
    for backend in CPU_BACKENDS[1:]:
        got = policy.l1_stage(geom, l1, reqs, jnp.int32(5),
                              backend=backend)
        _tree_equal(got, ref)


# ---------------------------------------------------------------------------
# end to end: SimResult equality on solo / mix / non-ideal NoC points
# ---------------------------------------------------------------------------
def _small_app(app, **over):
    return dataclasses.replace(APPS[app], rounds=96, **over)


@pytest.mark.parametrize("arch", ["ata", "ata_bypass", "victim"])
def test_simulate_backend_invariant_solo(arch):
    tr = make_trace(_small_app("cfd"))
    ref = simulate(arch, tr, probe_backend="lax")
    assert ref.ipc > 0
    for backend in CPU_BACKENDS[1:]:
        assert simulate(arch, tr, probe_backend=backend) == ref


def test_simulate_backend_invariant_padded_round():
    """R = 30 * 5 = 150 requests per round — not a multiple of the
    kernel tile. Pad lanes must be dead in the arbitration too, not
    just in the probe."""
    tr = make_trace(_small_app("cfd", m=5))
    ref = simulate("ata", tr, probe_backend="lax")
    assert simulate("ata", tr, probe_backend="pallas_interpret") == ref


def test_simulate_backend_invariant_mix_and_noc():
    mix = WorkloadMix(apps=(_small_app("cfd"), _small_app("HS3D")))
    tr = mix.compose()
    ref = simulate("ata", tr, probe_backend="lax")
    assert simulate("ata", tr, probe_backend="pallas_interpret") == ref

    solo = make_trace(_small_app("cfd"))
    ref_noc = simulate("ata", solo, noc="crossbar", probe_backend="lax")
    assert simulate("ata", solo, noc="crossbar",
                    probe_backend="pallas_interpret") == ref_noc


# ---------------------------------------------------------------------------
# sweep axis semantics
# ---------------------------------------------------------------------------
def test_sweep_grid_backend_axis_bitexact_and_buckets_apart():
    tr = make_trace(_small_app("cfd"))
    grid = SweepGrid(["ata"], [PAPER_GEOMETRY], [tr],
                     probe_backends=CPU_BACKENDS)
    run = grid.run()
    assert run.report.n_points == 3
    # backends lower different programs: one executable each
    assert run.report.n_executables == 3
    ref = simulate("ata", tr, probe_backend="lax")
    for point, res in zip(grid.points, run.results):
        assert point.probe_backend in CPU_BACKENDS
        assert res == ref


def test_sweep_point_backend_defaults_to_lax():
    tr = make_trace(_small_app("cfd"))
    assert SweepPoint("ata", PAPER_GEOMETRY, tr,
                      "ideal").probe_backend == "lax"
    assert DEFAULT_PROBE_BACKEND == "lax"
    assert PROBE_BACKENDS == ("lax", "lax_unfused", "pallas",
                              "pallas_interpret")


def test_unknown_backend_rejected():
    tr = make_trace(_small_app("cfd"))
    with pytest.raises(ValueError, match="probe_backend"):
        simulate("ata", tr, probe_backend="fancy")
    with pytest.raises(ValueError, match="probe_backend"):
        check_probe_backend("lax ")
    with pytest.raises(ValueError, match="probe_backend"):
        SweepGrid(["ata"], [PAPER_GEOMETRY], [tr],
                  probe_backends=["lax", "fancy"])


# ---------------------------------------------------------------------------
# the rounds/sec regression gate (benchmarks.sim_speed reports)
# ---------------------------------------------------------------------------
def _simspeed_report(rps_lax=4500.0, rps_unfused=4200.0, execs=7,
                     rounds=64):
    from repro.core.report import compare_simspeed  # noqa: F401
    return {
        "kind": "simspeed", "schema": 1,
        "config": {"app": "cfd", "kernel": 0, "arch": "ata",
                   "rounds": rounds, "n_geoms": 13},
        "sweep": {"n_executables": 2 * execs},
        "cells": [
            {"backend": "lax", "rounds_per_sec": rps_lax, "wall_s": 1.0,
             "n_points": 13, "rounds": rounds, "n_executables": execs},
            {"backend": "lax_unfused", "rounds_per_sec": rps_unfused,
             "wall_s": 1.0, "n_points": 13, "rounds": rounds,
             "n_executables": execs},
        ],
        "headline": {"fused_speedup": rps_lax / rps_unfused},
    }


def test_compare_simspeed_gates_the_ratio_one_sided():
    from repro.core.report import compare_simspeed
    base = _simspeed_report(rps_lax=4500.0, rps_unfused=4200.0)  # 1.07x
    assert compare_simspeed(base, base) == []
    # absolute throughput halves on a slower host: ratio intact -> OK
    slower_host = _simspeed_report(rps_lax=2250.0, rps_unfused=2100.0)
    assert compare_simspeed(base, slower_host) == []
    # a *faster* fused path is never a regression
    better = _simspeed_report(rps_lax=6000.0, rps_unfused=4200.0)
    assert compare_simspeed(base, better) == []
    # fused win collapses below the floor -> fail
    lost = _simspeed_report(rps_lax=2900.0, rps_unfused=4200.0)  # 0.69x
    fails = compare_simspeed(base, lost, speedup_rtol=0.30)
    assert any("fused speedup fell" in f for f in fails)
    # within the tolerance band -> OK
    drifted = _simspeed_report(rps_lax=4000.0, rps_unfused=4200.0)
    assert compare_simspeed(base, drifted, speedup_rtol=0.30) == []


def test_compare_simspeed_structural_failures():
    from repro.core.report import compare_simspeed
    base = _simspeed_report()
    missing = _simspeed_report()
    missing["cells"] = missing["cells"][:1]
    del missing["headline"]["fused_speedup"]
    fails = compare_simspeed(base, missing)
    assert any("backend missing" in f for f in fails)
    assert any("headline missing" in f for f in fails)

    grown = _simspeed_report(execs=9)
    assert any("executable count grew" in f
               for f in compare_simspeed(base, grown))

    other_cfg = _simspeed_report(rounds=96)
    assert any("config mismatch" in f
               for f in compare_simspeed(base, other_cfg))

    not_simspeed = dict(base, kind="sensitivity")
    assert any("not a simspeed report" in f
               for f in compare_simspeed(base, not_simspeed))

    # absolute rounds/sec is gated only when opted in
    slow = _simspeed_report(rps_lax=2250.0, rps_unfused=2100.0)
    assert compare_simspeed(base, slow) == []
    fails = compare_simspeed(base, slow, rps_rtol=0.25)
    assert sum("rounds/sec fell" in f for f in fails) == 2


def test_sim_speed_benchmark_reports_and_self_gates(tmp_path):
    """One tiny end-to-end run of benchmarks.sim_speed: the report it
    writes must carry every gated field and pass its own gate."""
    from benchmarks import sim_speed
    from repro.core.report import compare_simspeed
    path = str(tmp_path / "simspeed.json")
    rep = sim_speed.run(rounds=16, reps=1, geoms=[SMALL],
                        out_json=path)
    assert rep["kind"] == "simspeed"
    assert {c["backend"] for c in rep["cells"]} \
        == {"lax", "lax_unfused"}
    assert all(c["rounds_per_sec"] > 0 for c in rep["cells"])
    assert rep["headline"]["fused_speedup"] > 0
    import json as _json
    with open(path) as f:
        on_disk = _json.load(f)
    assert compare_simspeed(on_disk, rep) == []


def test_compiled_pallas_backend_raises_off_tpu():
    """``pallas`` is the Mosaic-compiled kernel only: off-TPU, asking
    for it raises instead of falling back to the interpreter."""
    assert jax.default_backend() != "tpu"
    tr = make_trace(_small_app("cfd"))
    with pytest.raises(RuntimeError, match="needs a TPU"):
        simulate("ata", tr, probe_backend="pallas")
    with pytest.raises(RuntimeError, match="needs a TPU"):
        SweepGrid(["ata"], [PAPER_GEOMETRY], [tr],
                  probe_backends=["pallas"])


def test_non_ata_archs_ignore_backend():
    """The axis is ATA-family-only: other policies accept and ignore
    it, so one grid can mix families without a signature split."""
    tr = make_trace(_small_app("cfd"))
    for arch in ("private", "remote", "decoupled"):
        ref = simulate(arch, tr, probe_backend="lax")
        assert simulate(arch, tr, probe_backend="pallas_interpret") == ref
