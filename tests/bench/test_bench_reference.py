"""The plain references equal the program at small sizes on the CPU."""

import numpy as np
import pytest

from bench import harness
from bench.entries import sweep as sweep_entry
from bench.reference import sim_ref
from bench.traffic import sim_traces

PAPER = harness.load_json(harness.BENCH, "configs", "paper_gpu.json")
APPS = sim_traces.app_table(PAPER)
#: a small machine whose caches fill, evict and write back within a
#: few hundred rounds
SMALL = dict(PAPER["geometry"], n_cores=8, cluster_size=4, l1_sets=4,
             l1_ways=4, l1_banks=2, l2_parts=4, l2_sets=8, l2_ways=4)


@pytest.mark.parametrize("arch", ["private", "ata"])
@pytest.mark.parametrize("geom", [PAPER["geometry"], SMALL],
                         ids=["paper", "small"])
def test_sim_reference_equals_simulate(arch, geom):
    from repro.core import GpuGeometry, simulate
    from repro.core.simulator import Trace
    traces = [sim_traces.make_trace(dict(APPS[app], rounds=160),
                                    n_cores=geom["n_cores"], kernel=k,
                                    seed=5)
              for app, k in (("SN", 0), ("SN", 3), ("HS3D", 1))]
    refs = sim_ref.simulate(geom, arch, np.stack([t[0] for t in traces]),
                            np.stack([t[1] for t in traces]),
                            np.array([t[2] for t in traces]))
    for tr, ref in zip(traces, refs):
        got = sweep_entry.program_view(
            simulate(arch, Trace(*tr), GpuGeometry(**geom)))
        c, f = sweep_entry.gaps(got, sweep_entry.reference_view(
            ref, geom["n_cores"]))
        assert c == 0
        assert f < 1e-5


def test_sim_reference_counts_write_backs():
    """The small machine evicts dirty lines, so the write-back path of
    the reference is exercised, not just present."""
    tr = sim_traces.make_trace(dict(APPS["sradv1"], rounds=160),
                               n_cores=8, kernel=0, seed=1)
    ref, = sim_ref.simulate(SMALL, "private", tr[0][None], tr[1][None],
                            np.array([tr[2]]))
    assert ref["noc_flits"] > 4 * ref["l2"]


def test_sim_reference_imports_nothing_of_the_program():
    import ast
    tree = ast.parse(open(sim_ref.__file__).read())
    imported = {a.name.split(".")[0] for n in ast.walk(tree)
                if isinstance(n, ast.Import) for a in n.names}
    imported |= {n.module.split(".")[0] for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom) and n.module}
    assert imported <= {"numpy", "__future__"}, imported
