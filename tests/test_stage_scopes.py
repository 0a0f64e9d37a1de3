"""The names the benchmark reads out of the simulator: the round's stage
scopes in the optimised HLO, ``SweepGrid.run``'s host spans in a
profile, and ``sweep.compiled_hlo``."""
import dataclasses
import re

import jax
import pytest

from repro.core import APPS, SweepGrid, make_trace, sweep
from repro.core.probe import PROBE_SCOPE
from repro.core.simulator import ROUND_STAGES

OP_NAME = re.compile(r'op_name="([^"]*)"')


def _trace(rounds=8):
    return make_trace(dataclasses.replace(APPS["SN"], rounds=rounds))


@pytest.fixture
def fresh_keys(monkeypatch):
    """Executables dispatched inside the test, and no others."""
    monkeypatch.setattr(sweep, "_COMPILED_KEYS", {})


def _scopes(text):
    return {part for name in OP_NAME.findall(text)
            for part in name.split("/")}


@pytest.mark.parametrize("arch", ["private", "ata"])
def test_compiled_hlo_carries_every_round_stage(arch, fresh_keys):
    """Every stage scope survives into the optimised HLO's op_name
    metadata; ``probe`` only where the policy runs the aggregated probe.
    The grid stacks ``ideal`` with ``crossbar``: under ``ideal`` alone a
    ``private`` round sends no flits, and XLA folds its whole NoC stage
    away."""
    SweepGrid([arch], None, [_trace()], nocs=("ideal", "crossbar")).run()
    (module, text), = sweep.compiled_hlo()
    scopes = _scopes(text)
    assert set(ROUND_STAGES) <= scopes
    assert (PROBE_SCOPE in scopes) == (arch == "ata")


def test_probe_nests_in_l1(fresh_keys):
    SweepGrid(["ata"], None, [_trace()]).run()
    (_, text), = sweep.compiled_hlo()
    probes = [n for n in OP_NAME.findall(text) if PROBE_SCOPE in n.split("/")]
    assert probes
    for name in probes:
        parts = name.split("/")
        assert parts.index("l1") < parts.index(PROBE_SCOPE), name


def test_compiled_hlo_one_entry_per_dispatched_executable(fresh_keys):
    """Two buckets (m = 2 and m = 4 traces) under two archs: four
    executables, each reported once however often it ran, and each the
    program the sweep dispatched."""
    traces = [make_trace(dataclasses.replace(APPS[app], rounds=8))
              for app in ("SN", "cfd")]
    assert {t.addr.shape[2] for t in traces} == {2, 4}
    grid = SweepGrid(["private", "ata"], None, traces)
    grid.run()
    grid.run()
    hlo = sweep.compiled_hlo()
    assert len(hlo) == sweep.compile_count() == 4
    for (module, text), (fn, abstract) in zip(hlo,
                                              sweep._COMPILED_KEYS.values()):
        assert text.startswith(f"HloModule {module},")
        assert module == "jit_local_batch"
        assert text == fn.lower(abstract).compile().as_text()
    assert len({text for _, text in hlo}) == 4


def test_compiled_hlo_lowers_what_run_dispatched(fresh_keys):
    """The abstract arguments recorded at dispatch lower the same module
    as the concrete ones."""
    grid = SweepGrid(["ata"], None, [_trace()])
    grid.run()
    (fn, abstract), = sweep._COMPILED_KEYS.values()
    D, buckets, split = grid._prepare(None, None)
    (group, noc_group, _, kind, _), idxs = next(iter(buckets.items()))
    args = sweep._bucket_arrays([grid.points[i] for i in idxs], kind,
                                split, group, noc_group)
    assert fn.lower(args).as_text() == fn.lower(abstract).as_text()


def test_sweep_run_spans_nest(tmp_path):
    """A CPU profile of one two-bucket run holds the six ``sweep.*``
    spans: ``sweep.run`` around ``sweep.prepare`` and, per bucket,
    inputs, launch, fetch and summarize in turn."""
    from jax.profiler import ProfileData
    grid = SweepGrid(["private", "ata"], None, [_trace()])
    grid.run()
    jax.profiler.start_trace(str(tmp_path))
    try:
        grid.run()
    finally:
        jax.profiler.stop_trace()
    path, = tmp_path.glob("**/*.xplane.pb")
    spans = sorted((e.start_ns, e.end_ns, e.name)
                   for plane in ProfileData.from_file(str(path)).planes
                   for line in plane.lines for e in line.events
                   if e.name.startswith("sweep."))
    names = [n for _, _, n in spans]
    steps = ["sweep.inputs", "sweep.launch", "sweep.fetch",
             "sweep.summarize"]
    assert names == ["sweep.run", "sweep.prepare"] + steps * 2
    assert set(names) == set(sweep.RUN_SPANS)
    lo, hi, _ = spans[0]
    steps = spans[1:]
    for (s, e, _), (s2, _, _) in zip(steps, steps[1:]):
        assert e <= s2
    assert lo <= steps[0][0] and steps[-1][1] <= hi
