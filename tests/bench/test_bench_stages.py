"""The scope and idle split (``bench/stages.py``): device operations
matched to the round's stage scopes through the program's HLO, counted
over the traced window's grid runs, idle time cut at the sweep's host
spans."""
import collections
import json
import types

import pytest

from bench import harness, stages, tracing



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
# on the host's clock, as tracing.load leaves them where clocks pair
MODULES = [(1000, 4000, "jit_local_batch(77)"), (5000, 5300, "jit_stack(12)")]


def _trace(ops=None, modules=MODULES, paired=True):
    return {"ops": {0: ops if ops is not None else _ops()},
            "modules": {0: modules}, "issued": [900, 4900],
            "spans": [(0, 10000, "bench.window"),
                      (100, 9500, "bench.unit")] + SPANS,
            "paired": {0: paired}, "skew_ns": {0: 100 if paired else 0}}


def _split(hlo, ops=None, modules=MODULES, paired=True, err=None):
    return stages.split(_trace(ops, modules, paired), hlo, [0], [1000], 1,
                        err=err)


def _ns(r, scope):
    """A scope's time in ns (the synthetic unit has 1000 requests)."""
    v = stages.scope_ns_per_req(r, scope)
    return None if v is None else round(v * 1000, 6)


def test_synthetic_stage_and_idle_sums(capsys):
    r = _split([_hlo("jit_local_batch", INSTRS)])
    ns = {s: _ns(r, s) for s in ("l1", "probe", "l2", "fill", "noc",
                                 "timing", "while")}
    assert ns == {"l1": 1000, "probe": 500, "l2": 500, "fill": 500,
                  "noc": 100, "timing": 500, "while": 2600}
    assert r["requests"] == 1000
    assert sum(r["scopes"].values()) == pytest.approx(3000e-9)
    assert r["self_s"] == pytest.approx(3200e-9)
    assert r["mapped_s"] == pytest.approx(3000e-9)
    idle = {k: round(v * 1e9, 6) for k, v in r["idle"].items()}
    assert idle == {"bench": 200 + 1000, "sweep.prepare": 400,
                    "sweep.inputs": 300, "sweep.launch": 100,
                    "sweep.fetch": 300, "sweep.summarize": 500,
                    "sweep.run": 200 + 3800}
    assert sum(r["idle"].values()) + 3200e-9 == pytest.approx(r["window_s"])
    assert stages.idle_share(r, "sweep.inputs") == pytest.approx(3.0)
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
    assert _ns(_split(hlo), "l1") == 1000
    ops = [(s, e, _event("fusion.1", f16) if "fusion.1 " in n else n)
           for s, e, n in _ops()]
    r = _split(hlo, ops)
    assert _ns(r, "l1") == 500 and _ns(r, "probe") == 500
    assert _ns(r, "timing") == 1000


def test_same_text_two_stages_is_no_split(capsys):
    """Two executables hold one text under different scopes: those
    scopes read nothing, the others still read."""
    other = [(n, t, f"{LOOP}/timing/add" if n == "fusion.1" else o)
             for n, t, o in INSTRS]
    hlo = [_hlo("jit_local_batch", other), _hlo("jit_local_batch", INSTRS)]
    r = _split(hlo)
    assert stages.scope_ns_per_req(r, "l1") is None
    assert stages.scope_ns_per_req(r, "timing") is None
    assert "are and are not under 'l1'" in capsys.readouterr().err
    assert _ns(r, "l2") == 500 and _ns(r, "probe") == 500
    assert r["idle"] is not None


def test_unmatched_instruction_is_no_split(capsys):
    ops = _ops() + [(3600, 3700, "%fusion.99 = f32[8] fusion(%p)")]
    r = _split([_hlo("jit_local_batch", INSTRS)], ops)
    assert r["scopes"] is None and stages.scope_ns_per_req(r, "l2") is None
    assert "fusion.99" in capsys.readouterr().err


def test_name_matches_but_text_differs_is_no_split(capsys):
    """The HLO read after the window is compiled again; where it is not
    the program that ran, an instruction of the same name prints other
    text, and nothing is split."""
    ops = [(s, e, _event("fusion.3", "f32[8] fusion(%q), kind=kInput, "
                         "calls=%fc") if "fusion.3 " in n else n)
           for s, e, n in _ops()]
    r = _split([_hlo("jit_local_batch", INSTRS)], ops)
    assert r["scopes"] is None and r["idle"] is not None
    assert "text of fusion.3 differs" in capsys.readouterr().err


def test_no_dispatched_module_ran_is_no_split(capsys):
    r = _split([_hlo("jit_other", INSTRS)])
    assert r["scopes"] is None and r["requests"] is None
    assert "0 executions of dispatched programs for 1 launches" in \
        capsys.readouterr().err


def test_operation_outside_every_module_event(capsys):
    """Where the profiler lost a module event, the operations outside
    every module event are matched by their text over every dispatched
    executable, and make one execution. More executions than launches
    cannot be told apart, before the last module event or after it."""
    f3 = _event("fusion.3", F8)
    lost = _split([_hlo("jit_local_batch", INSTRS)], modules=MODULES[1:])
    assert lost["requests"] == 1000 and _ns(lost, "l1") == 1000
    assert _ns(lost, "l2") == 500 and lost["mapped_s"] == pytest.approx(
        3000e-9)
    for extra in ([(4500, 4600, f3)], [(5400, 5500, f3)]):
        r = _split([_hlo("jit_local_batch", INSTRS)], _ops() + extra)
        assert r["requests"] is None and r["scopes"] is None
        assert r["self_s"] == pytest.approx(3300e-9)
        assert "2 executions of dispatched programs for 1 launches" in \
            capsys.readouterr().err


def test_programs_the_trace_lost_leave_the_scopes_unread(capsys):
    """A trace holding fewer module events, with those lost, than the
    programs the host issued was cut by the profiler: no scope is read,
    though each launch shows its execution."""
    trace = _trace()
    trace["issued"] = trace["issued"] + [9000]
    r = stages.split(trace, [_hlo("jit_local_batch", INSTRS)], [0], [1000],
                     1)
    assert r["requests"] is None and r["scopes"] is None
    assert "2 module events and 0 lost for 3 programs issued" in \
        capsys.readouterr().err


def test_an_execution_that_lost_events_leaves_the_scopes_unread(capsys):
    """Every execution of one program runs as many operations. Where the
    trace holds fewer in one, the profiler lost events inside it, whose
    time would go to the loop holding them: its grid run is left out of
    the scopes, its time and its requests both, and where that is the
    only run no scope is read. The same two executions whole read the
    time of both; in two grid runs, the whole one is read alone."""
    second = [(s + 5000, e + 5000, n) for s, e, n in _ops()[:-1]]

    def split(ops, run_requests=(1000,)):
        trace = _trace(ops, MODULES + [(6000, 9000, "jit_local_batch(77)")])
        trace["issued"] = [900, 4900, 5950]
        trace["spans"].append((5900, 6000, "sweep.launch"))
        if len(run_requests) == 2:
            trace["spans"] = [x if x[2] != "sweep.run" else
                              (200, 5800, "sweep.run")
                              for x in trace["spans"]] + [
                (5800, 9000, "sweep.run")]
        return stages.split(trace, [_hlo("jit_local_batch", INSTRS)], [0],
                            list(run_requests), 1)
    whole = split(_ops() + second)
    assert whole["requests"] == 1000
    assert _ns(whole, "l2") == 1000 and _ns(whole, "l1") == 2000
    lossy = _ops() + second[:3] + second[4:]
    r = split(lossy)
    assert r["requests"] is None and stages.scope_ns_per_req(r, "l2") is None
    err = capsys.readouterr().err
    assert "runs [0] of 1 left out of the scopes: the profiler lost " \
        "events" in err and "[('jit_local_batch(77)', 6)]" in err
    assert "no scope read: every grid run lost events" in err
    both = split(_ops() + second, (1000, 1000))
    assert both["requests"] == 2000 and _ns(both, "l2") == 500
    r = split(lossy, (1000, 3000))
    assert r["requests"] == 1000
    assert _ns(r, "l2") == 500 and _ns(r, "l1") == 1000
    assert r["idle"] is not None
    assert "runs [1] of 2 left out" in capsys.readouterr().err


def test_failed_pairing_gives_no_idle_split(capsys):
    """Clocks that could not be paired leave the idle split unread; the
    scopes do not need them."""
    r = _split([_hlo("jit_local_batch", INSTRS)], paired=False)
    assert r["idle"] is None and not r["paired"] and r["skew_ns"] == [0]
    assert stages.idle_share(r, "sweep.prepare") is None
    assert _ns(r, "l1") == 1000
    assert "pairing failed" in capsys.readouterr().err


def test_without_hlo_there_is_no_stage_split(tmp_path, monkeypatch,
                                             recorded):
    """An entry that gives no program HLO gets the busy and idle
    reduction and no split."""
    import types
    monkeypatch.setattr(harness, "TRACE_DIR", recorded.dir(tmp_path))
    got = harness._reduce_trace(types.SimpleNamespace(), None, [0], 1,
                                err=None)
    assert got["split"] is None and got["trace"]["busy_s"][0] > 0
    got = harness._reduce_trace(
        types.SimpleNamespace(compiled_hlo=recorded.hlo),
        types.SimpleNamespace(run_requests=recorded.runs), [0], 1, err=None)
    assert got["split"]["requests"] == 1920 and got["reduce_s"] > 0


def test_signature_and_stage_of():
    """A device event types each operand and leaves out metadata and
    backend configuration; its signature equals its HLO line's. A scope
    counts every operation whose op_name holds it as a component."""
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
    names = [f"{LOOP}/l1/probe/jit(argsort)/iota", f"{LOOP}/l2/jit(argsort)"
             "/iota", f"{LOOP}/add", "", f"{LOOP}/l1x/add"]
    r = {"named": {c for n in names for c in n.split("/")}, "requests": 1000,
         "scopes": collections.Counter({frozenset([n]): 1e-6 * (i + 1)
                                        for i, n in enumerate(names)})}
    assert stages.scope_ns_per_req(r, "probe") == pytest.approx(1.0)
    assert stages.scope_ns_per_req(r, "l1") == pytest.approx(1.0)
    assert stages.scope_ns_per_req(r, "l2") == pytest.approx(2.0)
    assert stages.scope_ns_per_req(r, "while") == pytest.approx(11.0)


def _recorded_trace(recorded, path):
    return tracing.load(tracing.find_xplane(recorded.dir(path)))


def test_recorded_chip_trace(tmp_path, recorded):
    """A trace recorded on a TPU v5e by
    ``bench/testdata/record_stage_trace.py``: single-point ``private``
    and ``ata`` grids under the benchmark's spans, with the HLO of both
    executables (the trace is kept gzipped)."""
    trace = _recorded_trace(recorded, tmp_path)
    r = stages.split(trace, recorded.hlo(), [0], recorded.runs, 1)
    busy = tracing.reduce(trace, [0])["busy_s"][0]
    assert r["paired"] and r["requests"] == 1920
    for scope, s in recorded.scopes_s.items():
        assert stages.scope_ns_per_req(r, scope) == pytest.approx(
            1e9 * s / 1920, rel=1e-9), scope
    for span, s in recorded.idle_s.items():
        assert stages.idle_share(r, span) == pytest.approx(
            100 * s / recorded.window_s, rel=1e-9), span
    assert r["mapped_s"] == pytest.approx(r["self_s"], rel=1e-9)
    assert r["self_s"] == pytest.approx(busy, rel=0.02)
    assert sum(r["idle"].values()) + busy == pytest.approx(
        r["window_s"], rel=1e-9)


@pytest.mark.parametrize("cut,held", [
    ("module", 1920), ("first module", 1920),
    ("module and its operations", None),
    ("first module and its operations", None),
    ("module closed at the cut, later programs lost", None),
    ("module and the end of its operations, later programs lost", None)])
def test_partial_trace_counts_the_runs_it_holds(tmp_path, cut, held, capsys,
                                                recorded):
    """Where the trace lost module events, the scopes divide the time of
    the window's grid runs by their requests where the trace holds every
    run, or read nothing: never a part of the runs by the whole unit's
    requests. A module event lost mid-trace leaves its operations,
    matched by their text; the profiler's cut loses the programs issued
    after it, and a run it cut or lost leaves no scope read."""
    trace = _recorded_trace(recorded, tmp_path)
    mods = trace["modules"][0]
    first, second = [m for m in mods if m[2].startswith("jit_local_batch")]
    mid = (second[0] + second[1]) / 2
    if cut.startswith("first module"):
        mods.remove(first)
        if cut != "first module":
            trace["ops"][0] = [ev for ev in trace["ops"][0]
                               if not first[0] <= ev[0] < first[1]]
    elif cut.startswith("module closed at the cut"):
        trace["ops"][0] = [ev for ev in trace["ops"][0] if ev[1] < mid]
        mods[-1] = (second[0], max(e for _, e, _ in trace["ops"][0]),
                    second[2])
    else:
        mods.remove(second)
        if cut != "module":
            end = mid if "end" in cut else second[0]
            trace["ops"][0] = [ev for ev in trace["ops"][0] if ev[0] < end]
    if cut.endswith("later programs lost"):
        trace["issued"] = trace["issued"] + [trace["issued"][-1] + 1e5]
    r = stages.split(trace, recorded.hlo(), [0], recorded.runs, 1)
    assert r["requests"] == held
    if held is None:
        assert stages.scope_ns_per_req(r, "l2") is None
        assert "no scope read" in capsys.readouterr().err
        return
    for scope, v in recorded.scopes_s.items():
        assert stages.scope_ns_per_req(r, scope) == pytest.approx(
            1e9 * v / 1920, rel=1e-9), scope


def _profile_without(path, lost):
    """The profile at ``path`` as ``ProfileData.from_file`` gives it, with
    the module events at the indices ``lost`` of each device dropped and
    their operations kept, as the profiler now and then leaves them."""
    import types
    from jax.profiler import ProfileData

    def ev(e):
        return types.SimpleNamespace(start_ns=e.start_ns, end_ns=e.end_ns,
                                     name=e.name)
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = sorted((ev(e) for e in line.events),
                            key=lambda e: e.start_ns)
            if line.name == tracing.MODULES_LINE:
                events = [e for i, e in enumerate(events) if i not in lost]
            lines.append(types.SimpleNamespace(name=line.name,
                                               events=events))
        planes.append(types.SimpleNamespace(name=plane.name, lines=lines))
    return types.SimpleNamespace(planes=planes)


@pytest.mark.parametrize("lost,paired", [
    ((37,), True), ((75,), True), ((37, 75), True), ((10,), False),
    ((10, 37), False)])
def test_a_lost_module_event_keeps_the_clocks_paired(
        tmp_path, monkeypatch, recorded, lost, paired):
    """The profiler lost a module event of a grid run (the recorded
    trace's modules 37 and 75) and kept its operations: those operations
    stand for its start, so the device's clock still moves onto the
    host's and the idle split reads what the whole trace gives. A
    staging program's module event (module 10) holds no operation
    events, so where it is lost nothing stands for it, and the clocks
    do not pair."""
    import jax
    path = tracing.find_xplane(recorded.dir(tmp_path))
    whole = tracing.load(path)
    fake = _profile_without(path, set(lost))
    monkeypatch.setattr(jax.profiler, "ProfileData",
                        types.SimpleNamespace(from_file=lambda p: fake))
    trace = tracing.load(path)
    assert len(trace["modules"][0]) == len(whole["modules"][0]) - len(lost)
    assert trace["paired"][0] is paired
    r = stages.split(trace, recorded.hlo(), [0], recorded.runs, 1)
    if not paired:
        assert trace["skew_ns"][0] == 0.0 and r["idle"] is None
        assert stages.idle_share(r, "sweep.prepare") is None
        assert r["scopes"] is None
        return
    assert trace["skew_ns"][0] == pytest.approx(whole["skew_ns"][0],
                                                abs=1000)
    assert r["requests"] == 1920
    for span, s in recorded.idle_s.items():
        assert stages.idle_share(r, span) == pytest.approx(
            100 * s / recorded.window_s, rel=1e-3), span
    for scope, s in recorded.scopes_s.items():
        assert stages.scope_ns_per_req(r, scope) == pytest.approx(
            1e9 * s / 1920, rel=1e-9), scope


def test_traced_split_line(tmp_path, monkeypatch, capsys, recorded):
    """The split printed after a traced run, from the run's own
    reduction: the stage times and ``other`` sum to the operations'
    self time per request, and the idle shares to at most the window's
    idle share."""
    trace = _recorded_trace(recorded, tmp_path)
    busy = tracing.reduce(trace, [0])["busy_s"][0]
    r = stages.split(trace, recorded.hlo(), [0], recorded.runs, 1)
    result = {"correct": True, "attempted": 1920, "failed": 0, "metrics": {
        "sim.device_ns_per_req": {"value": 1e9 * busy / 1920, "unit": "ns"},
        "sim.idle_share": {"value": 100.0 * (1 - busy / r["window_s"]),
                           "unit": "%"}}}

    def run_cell(*a, **k):
        return result, {"split": r, "reduce_s": 1.5}
    monkeypatch.setattr(harness, "run_cell", run_cell)
    wl = {"chips": 1}
    line = stages.traced_split({}, "sim_ata_hi_points", seed=1, seconds=1,
                               t0=0.0, files=(wl, None, None))
    assert json.loads(capsys.readouterr().out.splitlines()[-1]) == line
    assert line["paired"] and line["mapped_share"] >= 90.0
    assert line["requests"] == 1920 and line["reduce_s"] == 1.5
    s = line["stages_ns_per_req"]
    assert set(s) == {"l1", "probe", "l2", "fill", "noc", "timing", "other"}
    top = sum(v for k, v in s.items() if k != "probe")
    assert top == pytest.approx(line["device_ns_per_req"], rel=0.02)
    idle = line["idle_share_by_span"]
    assert sum(idle.values()) == pytest.approx(line["idle_share"])
    assert sum(idle[k] for k in recorded.idle_s) <= line["idle_share"]
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
    assert line["stages_ns_per_req"] is None and line["reduce_s"] > 0


def test_main_refuses_without_a_tpu(capsys):
    assert stages.main(["--workload", "sim_ata_hi_points", "--seed", "1",
                        "--seconds", "1"]) == 3
    assert "no TPU" in capsys.readouterr().err
