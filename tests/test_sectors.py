"""Sectored lines through the normal entry points: what a sectored
geometry accepts and refuses, whole-line masks as the default, the
sector counters in telemetry, and the ``sector`` scope in the round's
HLO. (Program against the plain reference: tests/bench.)"""
import dataclasses
import re

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (GpuGeometry, SweepGrid, TelemetryConfig, simulate,
                        simulate_batch)
from repro.core import simulator
from repro.core.geometry import split_geometry
from repro.core.simulator import Trace

GEOM = GpuGeometry(n_cores=8, cluster_size=4, l1_sets=2, l1_ways=4,
                   l1_banks=2, l2_parts=2, l2_sets=4, l2_ways=2,
                   sectors_per_line=4)
WHOLE = dataclasses.replace(GEOM, sectors_per_line=1)


def _trace(sect=True, rounds=16, seed=0):
    rng = np.random.default_rng(seed)
    shape = (rounds, GEOM.n_cores, 4)
    addr = rng.integers(0, 48, shape).astype(np.int32)
    is_write = rng.random(shape) < 0.1
    masks = rng.integers(1, 16, shape) if sect else None
    return Trace(addr, is_write, 6.0, sect=masks)


@pytest.mark.parametrize("arch", ["remote", "decoupled", "ata_bypass",
                                  "ata_fifo", "ciao", "victim"])
def test_other_policies_refuse_sectored_lines(arch):
    with pytest.raises(ValueError, match="does not model sectored lines"):
        simulate(arch, _trace(), GEOM)
    with pytest.raises(ValueError, match="does not model sectored lines"):
        SweepGrid([arch], [GEOM], [_trace()])


def test_pallas_backends_refuse_sectored_lines():
    trace = _trace()
    with pytest.raises(ValueError, match="sector"):
        SweepGrid(["ata"], [GEOM], [trace],
                  probe_backends=["pallas_interpret"])
    with pytest.raises(ValueError, match="sector"):
        simulate("ata", trace, GEOM, probe_backend="pallas_interpret")
    # the compiled kernel needs a TPU before anything else; its sector
    # check is the same one
    for backend in ("pallas", "pallas_interpret"):
        with pytest.raises(ValueError, match="sector"):
            simulator.check_sectors("ata", 4, trace, backend)
    simulator.check_sectors("ata", 4, trace, "lax_unfused")


def test_pallas_path_refuses_sectored_requests():
    """The guard inside the probe itself, for callers that skip the
    entry points' checks."""
    from repro.core.probe import fused_probe_rank
    structure, scalars = split_geometry(GEOM)
    geom = simulator.TracedGeometry(structure, scalars)
    l1 = simulator._l1_state(geom, [simulator.get_arch("ata")])
    reqs = simulator._request_batch(
        geom, jnp.zeros((8, 4), jnp.int32), jnp.zeros((8, 4), bool),
        jnp.ones((8, 4), jnp.uint8))
    with pytest.raises(ValueError, match="whole lines"):
        fused_probe_rank(geom, l1, reqs, backend="pallas_interpret")


def test_a_mask_on_whole_lines_is_refused():
    trace = _trace()
    for run in (lambda: simulate("ata", trace, WHOLE),
                lambda: simulate_batch("private", [trace], WHOLE),
                lambda: SweepGrid(["ata"], [WHOLE], [trace])):
        with pytest.raises(ValueError, match="whole lines"):
            run()


def test_masks_beyond_the_line_are_refused():
    trace = _trace()
    two = dataclasses.replace(GEOM, sectors_per_line=2)
    with pytest.raises(ValueError, match="beyond the line"):
        simulate("private", trace, two)
    with pytest.raises(ValueError, match="1..8"):
        simulate("private", _trace(sect=False),
                 dataclasses.replace(GEOM, sectors_per_line=9))


@pytest.mark.parametrize("bad,match", [
    (np.full((16, 8, 4), 1.0), "integer"),
    (np.ones((16, 8, 3), np.int32), "shape"),
    (np.zeros((16, 8, 4), np.int32), "1..255"),
    (np.full((16, 8, 4), 256), "1..255")])
def test_trace_checks_its_masks(bad, match):
    t = _trace(sect=False)
    with pytest.raises(ValueError, match=match):
        Trace(t.addr, t.is_write, 6.0, sect=bad)


def test_trace_keeps_masks_as_uint8_through_replace():
    t = _trace()
    assert t.sect.dtype == np.uint8
    assert np.array_equal(t._replace(insn_per_req=7.0).sect, t.sect)


@pytest.mark.parametrize("arch", ["private", "ata"])
def test_no_mask_means_the_whole_line(arch):
    bare = _trace(sect=False)
    full = bare._replace(sect=np.full(bare.addr.shape, 15))
    a, b = simulate(arch, bare, GEOM), simulate(arch, full, GEOM)
    assert a == b or all(x == y or (x != x and y != y)
                         for x, y in zip(a, b))
    assert a.l1_sector_misses == a.l2_sector_misses == 0.0
    assert a.dram_sectors == 4 * a.dram_accesses


def test_whole_line_results_report_no_sector_counters():
    r = simulate("ata", _trace(sect=False), WHOLE)
    assert (r.l1_sector_misses, r.l2_sector_misses, r.dram_sectors) \
        == (0.0, 0.0, 0.0)


def test_sector_counters_in_telemetry():
    trace = _trace(rounds=32)
    result, tl = simulate("ata", trace, GEOM,
                          telemetry=TelemetryConfig(window=8))
    for name in ("l1_sector_misses", "l2_sector_misses", "dram_sectors"):
        assert tl.total(name) == getattr(result, name), name
    assert result.l1_sector_misses > 0 and result.dram_sectors > 0
    tl.check(result)
    _, whole = simulate("ata", _trace(sect=False, rounds=32), WHOLE,
                        telemetry=TelemetryConfig(window=8))
    assert "dram_sectors" not in whole.cumulative


def _op_names(arch, geom, trace):
    structure, scalars = split_geometry(geom)
    pa = simulator._point_arrays(
        simulator._trace_arrays(trace, geom.sectors_per_line), scalars)
    text = simulator._simulate.lower(
        (arch,), ("ideal",), pa, structure, 1, "lax", None).as_text(
            debug_info=True)
    return set(re.findall(r'"([^"]*/sector[^"]*)"', text))


def test_sector_scope_nests_in_the_stages():
    names = _op_names("ata", GEOM, _trace())
    for stage in ("l1/probe/sector", "l2/sector", "fill/sector"):
        assert any(stage in n for n in names), stage
    assert not _op_names("ata", WHOLE, _trace(sect=False))


def test_unfused_probe_agrees_on_sectored_lines():
    trace = _trace(rounds=24, seed=3)
    a = simulate("ata", trace, GEOM)
    b = simulate("ata", trace, GEOM, probe_backend="lax_unfused")
    assert a.remote_hit_rate > 0 and a.l1_sector_misses > 0
    assert all(x == y or (x != x and y != y) for x, y in zip(a, b))
