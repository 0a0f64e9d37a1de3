"""Each entry's work unit and check run at a tiny size on the CPU, and
the harness drives a whole run there when told to skip the chip check."""
import glob
import io
import json
import os
import time

import numpy as np
import pytest

from bench import harness

SPEC = harness.load_json(harness.ROOT, "BENCHMARK.json")


@pytest.mark.parametrize("traffic_name", sorted(
    os.path.basename(p)[:-5]
    for p in glob.glob(os.path.join(harness.BENCH, "traffic", "*.json"))))
def test_unit_runs_and_checks(traffic_name, tiny):
    """Every traffic mix, named by a cell or kept for the program fault
    of PERF.md's open questions, runs and checks at a tiny size."""
    wl, config, _ = tiny(SPEC["workloads"][0]["name"])
    traffic = dict(harness.load_json(harness.BENCH, "traffic",
                                     traffic_name + ".json"),
                   apps=["b+tree", "SN"])
    c = harness.entry_module(config).Cell(config, traffic, seed=2**31 + 3)
    outputs = [c.unit(), c.unit()]
    checks, failed = c.check(outputs, config["limits"])
    assert failed == 0
    for name, (value, limit) in checks.items():
        assert value <= limit, name


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_run_cell_prints_one_result_line(cell, tiny):
    out, err = io.StringIO(), io.StringIO()
    result, _ = harness.run_cell(SPEC, cell, seed=7, seconds=0.5,
                                 trace=False, t0=time.perf_counter(),
                                 require_chip=False, files=tiny(cell),
                                 out=out, err=err)
    line = json.loads(out.getvalue().splitlines()[-1])
    assert line == json.loads(json.dumps(result))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    want = {m["name"] for m in harness.cell_metrics(SPEC, cell, False)}
    assert set(line["metrics"]) == want and "setup_s" in want
    assert line["metrics"]["setup_s"]["value"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # the compared numbers are the last lines of standard error
    tail = err.getvalue().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") and " limit " in t for t in tail)
    assert "compiles in the window: 0" in err.getvalue()


@pytest.mark.parametrize("runs", [3, None])
def test_traced_cell_runs_its_first_grid_runs(runs, tiny):
    """A traced run's cell holds the first ``traced_runs`` grid runs of
    the unit (every one where the traffic names none), with their
    points, and checks just those."""
    wl, config, traffic = tiny("sim_ata_hi_points")
    traffic = dict(traffic, traced_runs=runs)
    entry = harness.entry_module(config)
    whole = entry.Cell(config, traffic, seed=2**31 + 9)
    c = entry.Cell(config, traffic, seed=2**31 + 9, traced=True)
    n = runs or len(whole.grids)
    assert len(c.grids) == len(c.points) == n
    for (arch, tr), (want_arch, want) in zip(c.points, whole.points):
        assert arch == want_arch and all(
            np.array_equal(a, b) for a, b in zip(tr, want))
    assert c.run_requests == whole.run_requests[:n]
    assert c.requests_per_unit == sum(whole.run_requests[:n])
    checks, failed = c.check([c.unit()], config["limits"])
    assert failed == 0
    for name, (value, limit) in checks.items():
        assert value <= limit, name
