"""The sectored cell's pieces on the CPU: the program equals the plain
sectored reference exactly at a small size, the entry's check passes
the program and fails the bf16 control and a whole-line fault, and the
traffic generator's masks follow its rule."""
import copy

import numpy as np
import pytest

from bench import harness
from bench.entries import sweep_sectored
from bench.reference import sim_ref_sectored
from bench.traffic import sim_traces, sim_traces_sectored

GH100 = harness.load_json(harness.BENCH, "configs", "gh100_sectored.json")
TRAFFIC = harness.load_json(harness.BENCH, "traffic",
                            "ata_gh100_hi_points.json")
#: two clusters of 18, caches small enough to fill, evict and write
#: back within 48 rounds
SMALL = dict(GH100["geometry"], n_cores=36, cluster_size=18, l1_sets=4,
             l1_ways=8, l1_banks=2, l2_parts=4, l2_sets=8, l2_ways=4)


def _random_traces(seed, n=3, rounds=48, lines=160):
    rng = np.random.default_rng(seed)
    shape = (n, rounds, SMALL["n_cores"], 4)
    return (rng.integers(0, lines, shape).astype(np.int32),
            rng.random(shape) < 0.15,
            rng.integers(1, 16, shape).astype(np.uint8),
            np.array([6.0, 9.0, 13.0][:n]))


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("arch", ["private", "ata"])
def test_program_equals_the_sectored_reference(arch, seed):
    """``simulate`` and a stacked ``SweepGrid`` against the reference, on
    every counter exactly, the sector counters among them."""
    from repro.core import GpuGeometry, SweepGrid, simulate
    from repro.core.simulator import Trace
    addr, w, sect, insn = _random_traces(seed)
    geom = GpuGeometry(**SMALL)
    traces = [Trace(addr[p], w[p], insn[p], sect=sect[p])
              for p in range(len(insn))]
    refs = sim_ref_sectored.simulate(SMALL, arch, addr, w, sect, insn)
    stacked = SweepGrid([arch], [geom], traces).run(n_devices=1).results
    for tr, ref, grid_res in zip(traces, refs, stacked):
        want = sweep_sectored.reference_view(ref, SMALL["n_cores"])
        for res in (simulate(arch, tr, geom), grid_res):
            got = sweep_sectored.program_view(res)
            assert {k: got[k] for k in sweep_sectored.COUNTERS} \
                == {k: want[k] for k in sweep_sectored.COUNTERS}
            assert sweep_sectored.gaps(got, want)[1] < 1e-5
        assert ref["l1_sector_misses"] > 0 and ref["l2_sector_misses"] > 0
        if arch == "ata":
            assert ref["remote"] > 0


def _small_files():
    """The cell's configuration and traffic at the small machine, two
    apps of 48 rounds."""
    config = copy.deepcopy(GH100)
    config["geometry"] = dict(SMALL)
    config["app_defaults"]["rounds"] = 48
    traffic = dict(TRAFFIC, apps=["b+tree", "SN"])
    return config, traffic


@pytest.fixture(scope="module")
def small_cell():
    config, traffic = _small_files()
    return config, sweep_sectored.Cell(config, traffic, seed=2**31 + 11)


def test_check_passes_the_program(small_cell):
    config, cell = small_cell
    checks, failed = cell.check([cell.unit()], config["limits"])
    assert failed == 0
    assert checks["counter_gap"][0] == 0.0
    assert checks["float_rel_gap"][0] <= config["limits"]["float_rel_gap"]


def test_check_fails_the_bf16_control(small_cell):
    config, cell = small_cell
    checks, failed = cell.check_views([cell.control_views()],
                                      config["limits"])
    assert checks["counter_gap"][0] == 0.0
    assert checks["float_rel_gap"][0] > config["limits"]["float_rel_gap"]
    assert failed > 0


def test_check_fails_a_whole_line_program(small_cell, monkeypatch):
    """A program that takes every tag hit for a hit (sectors left out)
    fails on the counters."""
    import jax.numpy as jnp
    from repro.core import sweep, tagarray
    config, _ = small_cell
    monkeypatch.setattr(sweep, "_EXEC_MEMO", {})
    monkeypatch.setattr(tagarray, "sectors_at",
                        lambda state, *idx: jnp.full(
                            jnp.broadcast_shapes(*(jnp.shape(i)
                                                   for i in idx)), 255))
    cell = sweep_sectored.Cell(*_small_files(), seed=2**31 + 11)
    checks, failed = cell.check([cell.unit()], config["limits"])
    assert checks["counter_gap"][0] > config["limits"]["counter_gap"]
    assert failed > 0


def test_traffic_is_the_seeds_and_its_masks_follow_the_rule():
    apps = sim_traces.app_table(GH100)
    app = sim_traces_sectored.scaled_app(dict(apps["SN"], rounds=64), 4)
    assert app["ws_shared"] == 4 * apps["SN"]["ws_shared"]
    one = sim_traces_sectored.make_trace(app, n_cores=144, kernel=0,
                                         seed=2**31 + 5, sectors=4)
    again = sim_traces_sectored.make_trace(app, n_cores=144, kernel=0,
                                           seed=2**31 + 5, sectors=4)
    other = sim_traces_sectored.make_trace(app, n_cores=144, kernel=0,
                                           seed=2**31 + 6, sectors=4)
    for a, b in zip(one, again):
        assert np.array_equal(a, b)
    assert not np.array_equal(one[3], other[3])
    addr, _, _, sect = one
    assert sect.dtype == np.uint8 and sect.shape == addr.shape
    whole = sim_traces_sectored.whole_line_loads(app, n_cores=144, kernel=0,
                                                 seed=2**31 + 5)
    assert np.all(sect[whole] == 15)
    assert set(np.unique(sect[~whole])) == {1, 2, 4, 8}
    assert 0.05 < (~whole).mean() < 0.5
    # the addresses are make_trace's own, of the scaled app
    base = sim_traces.make_trace(app, n_cores=144, kernel=0, seed=2**31 + 5)
    assert np.array_equal(base[0], addr)


def test_cell_traffic_is_kernel_0_of_five_apps():
    traces = sim_traces_sectored.traffic_traces(GH100, TRAFFIC, seed=3)
    assert [(name, k) for name, k, _ in traces] == [
        (a, 0) for a in ("b+tree", "cfd", "doitgen", "conv3d", "SN")]
    assert [tr[0].shape[2] for _, _, tr in traces] == [2, 2, 4, 4, 4]
    assert sum(tr[0].size for _, _, tr in traces) == 3_538_944


def test_reference_imports_nothing_of_the_program():
    import ast
    for mod in (sim_ref_sectored,):
        tree = ast.parse(open(mod.__file__).read())
        imported = {a.name.split(".")[0] for n in ast.walk(tree)
                    if isinstance(n, ast.Import) for a in n.names}
        imported |= {n.module.split(".")[0] for n in ast.walk(tree)
                     if isinstance(n, ast.ImportFrom) and n.module}
        assert imported <= {"numpy", "__future__"}, imported
