"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's chip check and drives the rest of a run
at a tiny size on the CPU, with one fault planted in the program: a
step that returns its state unchanged, half of the batch left out, an
answer altered where it is produced. Every cell runs on one chip, so
none has an exchange between chips to leave out."""
import io
import time

import pytest

from bench import harness

SPEC = harness.load_json(harness.ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in SPEC["workloads"]]


def _run(cell, files):
    out, err = io.StringIO(), io.StringIO()
    return harness.run_cell(SPEC, cell, seed=13, seconds=0.2, trace=False,
                            t0=time.perf_counter(), require_chip=False,
                            files=files, out=out, err=err)[0]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_runs_are_correct(cell, tiny):
    assert _run(cell, tiny(cell))["correct"] is True


@pytest.mark.parametrize("cell", CELLS)
def test_step_returns_state_unchanged(cell, tiny, monkeypatch):
    from repro.core import simulator, sweep
    monkeypatch.setattr(sweep, "_EXEC_MEMO", {})
    monkeypatch.setattr(simulator, "_round",
                        lambda policy, nocs, noc_idx, geom, insn, core_app,
                        state, xs, **kw: (state, None))
    assert _run(cell, tiny(cell))["correct"] is False


@pytest.mark.parametrize("cell", CELLS)
def test_half_the_batch_left_out(cell, tiny, monkeypatch):
    from repro.core import sweep
    real = sweep.SweepGrid.run
    runs = []

    def half(self, *args, **kwargs):
        n = len(self.points)
        if n == 1 and len(runs) % 2:
            # single-point grids: every second one is left out, the
            # answer before it repeated
            runs.append(runs[-1])
            return runs[-1]
        run = real(sweep.SweepGrid.from_points(self.points[:n // 2 or 1]),
                   *args, **kwargs)
        rest = [run.results[i % len(run.results)]
                for i in range(n - len(run.results))]
        runs.append(run._replace(results=run.results + rest))
        return runs[-1]

    monkeypatch.setattr(sweep.SweepGrid, "run", half)
    assert _run(cell, tiny(cell))["correct"] is False


@pytest.mark.parametrize("cell", CELLS)
def test_answer_altered(cell, tiny, monkeypatch):
    from repro.core import sweep
    real = sweep._summarize

    def altered(stats, trace):
        res = real(stats, trace)
        return res._replace(dram_accesses=res.dram_accesses + 1)

    monkeypatch.setattr(sweep, "_summarize", altered)
    assert _run(cell, tiny(cell))["correct"] is False
