"""The round's L1 tag-state updates lower to the row form (masked
selects) wherever a policy writes only its requester's own L1, and to
scatters where the target is another array (``decoupled``'s home
cache). The L2 stage writes its packed state with one scatter a round,
on whole-line and sectored geometries."""
import dataclasses
import re

import numpy as np
import pytest

from repro.core import APPS, PAPER_GEOMETRY, SweepGrid, make_trace, sweep
from repro.core import simulator
from repro.core.geometry import GpuGeometry, split_geometry

#: Each StableHLO scatter's operand type (the first in its signature).
SCATTER = re.compile(r'"stablehlo\.scatter"\(.*?\}\) : \((tensor<[^>]*>)',
                     re.S)


def _scatter_operands(text, shape):
    """Scatter operands of ``shape``, with or without a leading batch
    axis."""
    dims = "x".join(map(str, shape))
    l1 = re.compile(rf"tensor<(?:\d+x)?{dims}x(?:i32|i1)>")
    return [t for t in SCATTER.findall(text) if l1.fullmatch(t)]


def _lowered(arch, batched):
    trace = make_trace(dataclasses.replace(APPS["SN"], rounds=8))
    assert trace.addr.shape[2] == 4
    if not batched:
        structure, scalars = split_geometry(PAPER_GEOMETRY)
        pa = simulator._point_arrays(simulator._trace_arrays(trace), scalars)
        return simulator._simulate.lower(
            (arch,), ("ideal",), pa, structure, 1, "lax", None).as_text()
    # SweepGrid's own executable: a two-point bucket, vmapped
    grid = SweepGrid([arch], None, [trace, trace])
    D, buckets, split = grid._prepare(1, None)
    (group, nocs, structure, kind, backend), idxs = next(
        iter(buckets.items()))
    assert len(idxs) == 2
    args = sweep._bucket_arrays([grid.points[i] for i in idxs], kind, split,
                                group, nocs)
    fn = sweep._sharded_executable(group, nocs, structure, D, kind[2],
                                   backend)
    return fn.lower(args).as_text()


@pytest.mark.parametrize("batched", [False, True], ids=["one", "vmapped"])
@pytest.mark.parametrize("arch", ["private", "ata", "decoupled"])
def test_l1_updates_scatter_only_where_the_target_is_another_array(
        arch, batched):
    g = PAPER_GEOMETRY
    text = _lowered(arch, batched)
    l1 = _scatter_operands(text, (g.n_cores, g.l1_sets, g.l1_ways))
    assert _l2_scatters(text, g) == 1
    if arch == "decoupled":
        assert l1, "the home-cache fill keeps the scatter form"
    else:
        assert l1 == [], l1


def _l2_scatters(text, g):
    """Scatters on the L2 state: on the packed ``(P, S, F * W)`` state,
    and none left on a per-field ``(P, S, W)`` one."""
    fields = 4 if g.sectors_per_line > 1 else 3
    assert _scatter_operands(text, (g.l2_parts, g.l2_sets, g.l2_ways)) == []
    return len(_scatter_operands(
        text, (g.l2_parts, g.l2_sets, fields * g.l2_ways)))


def test_sectored_l2_takes_one_scatter_a_round():
    """The full GH100 geometry, 32-byte sectors: the touches' and fills'
    sector merges ride in the same one scatter."""
    g = GpuGeometry(n_cores=144, cluster_size=18, l1_sets=32, l1_ways=64,
                    l1_banks=4, l2_parts=48, l2_sets=640, l2_ways=16,
                    sectors_per_line=4)
    rng = np.random.default_rng(0)
    shape = (4, g.n_cores, 4)
    trace = simulator.Trace(rng.integers(0, 1 << 16, shape).astype(np.int32),
                            rng.random(shape) < 0.1, 6.0,
                            sect=rng.integers(1, 16, shape))
    structure, scalars = split_geometry(g)
    pa = simulator._point_arrays(simulator._trace_arrays(trace, 4), scalars)
    text = simulator._simulate.lower(
        ("ata",), ("ideal",), pa, structure, 1, "lax", None).as_text()
    assert _l2_scatters(text, g) == 1
