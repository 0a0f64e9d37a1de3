"""The benchmark's traffic copies reproduce the program's generators at
seed 0, and other seeds draw other traffic of the same sizes."""
import numpy as np
import pytest

from bench import harness
from bench.traffic import sim_traces

PAPER = harness.load_json(harness.BENCH, "configs", "paper_gpu.json")
APPS = sim_traces.app_table(PAPER)


def test_app_table_is_the_programs():
    import dataclasses
    from repro.core.trace.apps import APPS as PROGRAM_APPS
    assert set(APPS) == set(PROGRAM_APPS)
    for name, p in PROGRAM_APPS.items():
        assert dataclasses.asdict(p) == {k: APPS[name][k]
                                         for k in dataclasses.asdict(p)}


@pytest.mark.parametrize("app,kernel", [("cfd", 0), ("cfd", 3), ("SN", 1),
                                        ("b+tree", 1), ("nw", 0),
                                        ("sradv1", 14)])
def test_sim_trace_matches_program_at_seed_0(app, kernel):
    from repro.core.trace import APPS as PROGRAM_APPS, make_trace
    addr, is_write, insn = sim_traces.make_trace(
        APPS[app], n_cores=30, kernel=kernel, seed=0)
    want = make_trace(PROGRAM_APPS[app], n_cores=30, kernel=kernel)
    assert np.array_equal(addr, want.addr)
    assert np.array_equal(is_write, want.is_write)
    assert insn == want.insn_per_req


def test_sim_trace_other_seeds_differ_same_sizes():
    a0, w0, i0 = sim_traces.make_trace(APPS["SN"], n_cores=30, kernel=2,
                                       seed=0)
    a1, w1, i1 = sim_traces.make_trace(APPS["SN"], n_cores=30, kernel=2,
                                       seed=2**31 + 7)
    assert a0.shape == a1.shape and a0.dtype == a1.dtype == np.int32
    assert i0 == i1
    assert not np.array_equal(a0, a1) and not np.array_equal(w0, w1)


def test_traffic_traces_cover_every_kernel():
    traffic = harness.load_json(harness.BENCH, "traffic", "ata_hi.json")
    traces = sim_traces.traffic_traces(PAPER, traffic, seed=0)
    assert len(traces) == 23
    assert sum(t[2][0].size for t in traces) == 3_594_240
