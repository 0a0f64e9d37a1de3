"""The stage and idle split (``bench/stages.py``): device operations
matched to the round's stage scopes through the program's HLO, idle
time cut at the sweep's host spans."""
import gzip
import json
import os

import pytest

from bench import harness, stages

STAGE_TRACE = os.path.join(harness.BENCH, "testdata", "stages.xplane.pb.gz")
STAGE_HLO = os.path.join(harness.BENCH, "testdata", "stages.hlo.json.gz")


def _line(name, text, op_name=None):
    meta = f', metadata={{op_name="{op_name}" stack_frame_id=3}}' \
        if op_name else ""
    return f"  %{name} = {text}{meta}"


def _hlo(module, instrs):
    """A module's HLO text: [(name, text after ' = ', op_name or None)]."""
    body = "\n".join(_line(*i) for i in instrs)
    return (module, f"HloModule {module}, is_scheduled=true\n\n"
                    f"ENTRY %main.1 () -> f32[8] {{\n{body}\n}}\n")


def _event(name, text):
    return f"%{name} = {text}"


LOOP = "jit(local_batch)/vmap()/while/body/closed_call"
F8 = "f32[8] fusion(%p), kind=kLoop, calls=%fc"
INSTRS = [("while", "(s32[]) while(%t), condition=%c, body=%b", None),
          ("fusion.1", F8, f"{LOOP}/l1/gather"),
          ("fusion.2", F8, f"{LOOP}/l1/probe/eq"),
          ("fusion.3", F8, f"{LOOP}/l2/scatter"),
          ("fusion.4", F8, f"{LOOP}/fill/scatter"),
          ("fusion.5", F8, f"{LOOP}/noc/reduce_sum"),
          ("fusion.6", F8, f"{LOOP}/timing/scatter-add")]


def _ops(shift=0):
    """Operations of one run of the dispatched module (host clock), then
    one of a staging program the sweep never dispatched."""
    ev = {n: _event(n, t) for n, t, _ in INSTRS}
    ops = [(1000, 4000, ev["while"]), (1000, 1500, ev["fusion.1"]),
           (1500, 2000, ev["fusion.2"]), (2000, 2500, ev["fusion.3"]),
           (2500, 3000, ev["fusion.4"]), (3000, 3100, ev["fusion.5"]),
           (3100, 3600, ev["fusion.6"]),
           (5000, 5200, "%stack.1 = f32[1] concatenate(%a, %b)")]
    return [(s + shift, e + shift, n) for s, e, n in ops]


SPANS = [(200, 9000, "sweep.run"), (200, 600, "sweep.prepare"),
         (600, 900, "sweep.inputs"), (900, 1000, "sweep.launch"),
         (1000, 4300, "sweep.fetch"), (4300, 4800, "sweep.summarize")]
# device clock 100 ns ahead of the host's: modules start 100 ns after
# their issue ends
MODULES = {0: [(1100, 4100, "jit_local_batch(77)"),
               (5100, 5300, "jit_stack(12)")]}
ISSUED = [1000, 5000]


def _reduce(hlo, ops=None, issued=ISSUED, err=None):
    trace = {"ops": {0: ops if ops is not None else _ops()},
             "spans": [(0, 10000, "bench.window"), (100, 9500, "bench.unit")]}
    return stages.reduce(trace, MODULES, issued, SPANS, hlo, [0],
                         err=err)


def test_synthetic_stage_and_idle_sums(capsys):
    r = _reduce([_hlo("jit_local_batch", INSTRS)])
    ns = {k: round(v * 1e9, 6) for k, v in r["stages"].items()}
    assert ns == {"l1": 500, "probe": 500, "l2": 500, "fill": 500,
                  "noc": 100, "timing": 500, "other": 400 + 200}
    assert sum(r["stages"].values()) == pytest.approx(r["self_s"])
    assert r["self_s"] == pytest.approx(sum(r["busy_s"]))
    assert r["mapped_s"] == pytest.approx(3000e-9)
    idle = {k: round(v * 1e9, 6) for k, v in r["idle"].items()}
    assert idle == {"bench": 200 + 1000, "sweep.prepare": 400,
                    "sweep.inputs": 300, "sweep.launch": 100,
                    "sweep.fetch": 300, "sweep.summarize": 500,
                    "sweep.run": 200 + 3800}
    assert sum(r["idle"].values()) + r["busy_s"][0] == pytest.approx(
        r["window_s"])
    assert r["skew_ns"] == [100] and r["paired"]
    assert "pairing held" in capsys.readouterr().err


def test_instruction_text_breaks_a_tie():
    """Two executables share a module name (m = 2 and m = 4); the
    event's text picks the one that ran."""
    f16 = "f32[16] fusion(%p), kind=kLoop, calls=%fc"
    other = [(n, f16 if n == "fusion.1" else t,
              f"{LOOP}/timing/add" if n == "fusion.1" else o)
             for n, t, o in INSTRS]
    hlo = [_hlo("jit_local_batch", other), _hlo("jit_local_batch", INSTRS)]
    assert _reduce(hlo)["stages"]["l1"] == pytest.approx(500e-9)
    ops = [(s, e, _event("fusion.1", f16) if "fusion.1 " in n else n)
           for s, e, n in _ops()]
    r = _reduce(hlo, ops)
    assert r["stages"]["l1"] == 0.0
    assert r["stages"]["timing"] == pytest.approx(1000e-9)


def test_same_text_two_stages_is_no_split(capsys):
    other = [(n, t, f"{LOOP}/timing/add" if n == "fusion.1" else o)
             for n, t, o in INSTRS]
    hlo = [_hlo("jit_local_batch", other), _hlo("jit_local_batch", INSTRS)]
    r = _reduce(hlo)
    assert r["stages"] is None
    assert r["idle"] is not None
    assert "unclear" in capsys.readouterr().err


def test_unmatched_instruction_is_no_split(capsys):
    ops = _ops() + [(3600, 3700, "%fusion.99 = f32[8] fusion(%p)")]
    r = _reduce([_hlo("jit_local_batch", INSTRS)], ops)
    assert r["stages"] is None
    assert "fusion.99" in capsys.readouterr().err


def test_name_matches_but_text_differs_is_no_split(capsys):
    """The HLO read after the window is compiled again; where it is not
    the program that ran, an instruction of the same name prints other
    text, and nothing is split."""
    ops = [(s, e, _event("fusion.3", "f32[8] fusion(%q), kind=kInput, "
                         "calls=%fc") if "fusion.3 " in n else n)
           for s, e, n in _ops()]
    r = _reduce([_hlo("jit_local_batch", INSTRS)], ops)
    assert r["stages"] is None and r["idle"] is not None
    assert "text of fusion.3 differs" in capsys.readouterr().err


def test_no_dispatched_module_ran_is_no_split(capsys):
    r = _reduce([_hlo("jit_other", INSTRS)])
    assert r["stages"] is None
    assert "no module the sweep dispatched" in capsys.readouterr().err


def test_operation_outside_every_module_event():
    """Where the profiler lost a module event, an operation is matched
    by its signature over every dispatched executable; one that none
    holds is other."""
    f3 = _event("fusion.3", F8)
    ops = _ops() + [(4500, 4600, f3),
                    (4700, 4750, "%copy.9 = f32[8] copy(%x)")]
    r = _reduce([_hlo("jit_local_batch", INSTRS)], ops)
    assert r["stages"]["l2"] == pytest.approx(600e-9)
    assert r["stages"]["other"] == pytest.approx(650e-9)
    assert r["mapped_s"] == pytest.approx(3100e-9)
    ops = _ops() + [(4500, 4600, _event("fusion.3", "f32[9] add(%a)"))]
    assert _reduce([_hlo("jit_local_batch", INSTRS)], ops)["stages"][
        "other"] == pytest.approx(700e-9)


def test_failed_pairing_gives_no_idle_split(capsys):
    """One issue event for two modules: the clocks stay apart (skew 0),
    operations and modules both on the device's clock."""
    r = _reduce([_hlo("jit_local_batch", INSTRS)], _ops(shift=100),
                issued=[1000])
    assert r["idle"] is None and not r["paired"] and r["skew_ns"] == [0]
    assert r["stages"]["l1"] == pytest.approx(500e-9)
    assert "pairing failed" in capsys.readouterr().err


def test_without_hlo_there_is_no_stage_split():
    r = _reduce(None)
    assert r["stages"] is None and r["idle"] is not None


def test_signature_and_stage_of():
    """A device event types each operand and leaves out metadata and
    backend configuration; its signature equals its HLO line's."""
    hlo = ('%pad_bitcast_fusion.35 = s32[120,4]{0,1:T(4,128)S(1)} '
           'fusion(%get-tuple-element.1345), kind=kLoop, '
           'calls=%fused_computation.77.clone.clone, backend_config={"flag_'
           'configs":[],"estimated_cycles":"1828"}, metadata={op_name='
           '"jit(local_batch)/vmap()/while/body/closed_call/fill/add" '
           'stack_frame_id=80}')
    event = ('%pad_bitcast_fusion.35 = s32[120,4]{0,1:T(4,128)S(1)} fusion('
             's32[1,120,3]{1,2,0:T(4,128)S(1)} %get-tuple-element.1345), '
             'kind=kLoop, calls=%fused_computation.77.clone.clone')
    assert stages.signature(hlo) == stages.signature(event) == (
        "pad_bitcast_fusion.35", "s32[120,4]{0,1:T(4,128)S(1)}", "fusion",
        ("kind=kLoop", "calls=%fused_computation.77.clone.clone"))
    tuple_shape = ('%w = (s32[]{:T(128)}, f32[2]{0}) while((s32[]{:T(128)}, '
                   'f32[2]{0}) %t), condition=%c, body=%b')
    assert stages.signature(tuple_shape)[1:] == (
        "(s32[]{:T(128)}, f32[2]{0})", "while", ("condition=%c", "body=%b"))
    assert stages.signature("%c = f32[] constant(1)") == (
        "c", "f32[]", "constant", ())
    assert stages.stage_of(f"{LOOP}/l1/probe/jit(argsort)/iota") == "probe"
    assert stages.stage_of(f"{LOOP}/l2/jit(argsort)/iota") == "l2"
    assert stages.stage_of(f"{LOOP}/add") == "other"
    assert stages.stage_of("") == "other"


def _recorded_hlo():
    with gzip.open(STAGE_HLO, "rt") as f:
        return [tuple(x) for x in json.load(f)]


def _traced_run_dir(tmp_path):
    """A profiler log directory holding the recorded trace, as a traced
    run leaves it."""
    dest = tmp_path / "plugins" / "profile" / "t"
    dest.mkdir(parents=True)
    with gzip.open(STAGE_TRACE) as f:
        (dest / "vm.xplane.pb").write_bytes(f.read())
    return str(tmp_path)


def _recorded(tmp_path):
    from bench import tracing
    path = tracing.find_xplane(_traced_run_dir(tmp_path))
    return stages.reduce_file(path, _recorded_hlo(), [0])


def test_recorded_chip_trace(tmp_path):
    """A trace recorded on a TPU v5e by
    ``bench/testdata/record_stage_trace.py``: single-point ``private``
    and ``ata`` grids under the benchmark's spans, with the HLO of both
    executables (the trace is kept gzipped)."""
    r = _recorded(tmp_path)
    assert r["paired"]
    s = r["stages"]
    for stage in ("l1", "probe", "l2", "fill", "timing"):
        assert s[stage] > 0, stage
    assert sum(s.values()) == pytest.approx(r["self_s"], rel=1e-9)
    assert r["mapped_s"] >= 0.9 * r["self_s"]
    assert r["self_s"] == pytest.approx(r["busy_s"][0], rel=0.02)
    assert sum(r["idle"].values()) + r["busy_s"][0] == pytest.approx(
        r["window_s"], rel=1e-9)
    assert r["idle"]["sweep.prepare"] > 0 and r["idle"]["sweep.inputs"] > 0


def test_traced_split_line(tmp_path, monkeypatch, capsys):
    """The split printed after a traced run: the stage times sum to the
    operations' self time, and the idle shares to at most the window's
    idle share."""
    from repro.core import sweep
    trace_dir = _traced_run_dir(tmp_path)
    r = _recorded(tmp_path / "again")
    busy = r["busy_s"][0]
    result = {"correct": True, "attempted": 1000, "failed": 0, "metrics": {
        "sim.device_ns_per_req": {"value": 1e9 * busy / 1000, "unit": "ns"},
        "sim.idle_share": {"value": 100.0 * (1 - busy / r["window_s"]),
                           "unit": "%"}}}
    monkeypatch.setattr(harness, "TRACE_DIR", trace_dir)
    monkeypatch.setattr(harness, "run_cell", lambda *a, **k: result)
    monkeypatch.setattr(sweep, "compiled_hlo", _recorded_hlo)
    wl = {"chips": 1}
    line = stages.traced_split({}, "sim_ata_hi_points", seed=1, seconds=1,
                               t0=0.0, files=(wl, None, None))
    assert json.loads(capsys.readouterr().out.splitlines()[-1]) == line
    assert line["paired"] and line["mapped_share"] >= 90.0
    s = line["stages_ns_per_req"]
    assert set(s) == {"l1", "probe", "l2", "fill", "noc", "timing", "other"}
    assert sum(s.values()) == pytest.approx(line["self_ns_per_req"])
    assert line["self_ns_per_req"] == pytest.approx(
        line["device_ns_per_req"], rel=0.02)
    idle = line["idle_share_by_span"]
    assert sum(idle.values()) == pytest.approx(line["idle_share"])
    assert sum(idle[k] for k in stages.STEPS) <= line["idle_share"]
    result["failed"] = 10
    line = stages.traced_split({}, "sim_ata_hi_points", seed=1, seconds=1,
                               t0=0.0, files=(wl, None, None))
    assert line["stages_ns_per_req"] is None
    assert line["idle_share_by_span"] is not None


def test_traced_split_runs_a_tiny_cell(tiny, tmp_path, monkeypatch, capsys):
    """A whole traced run and its split, on the CPU at a tiny size: the
    run's result line, then the split's (the CPU shows no TPU
    operations, so nothing is split)."""
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    spec = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = "sim_ata_hi_points"
    line = stages.traced_split(spec, cell, seed=2**31 + 5, seconds=0.1,
                               t0=0.0, require_chip=False, files=tiny(cell))
    result, split = capsys.readouterr().out.splitlines()[-2:]
    assert json.loads(result)["correct"] is True
    assert json.loads(split) == line
    assert line["workload"] == cell and line["correct"] is True
    assert line["stages_ns_per_req"] is None and line["hlo_s"] > 0


def test_main_refuses_without_a_tpu(capsys):
    assert stages.main(["--workload", "sim_ata_hi_points", "--seed", "1",
                        "--seconds", "1"]) == 3
    assert "no TPU" in capsys.readouterr().err
