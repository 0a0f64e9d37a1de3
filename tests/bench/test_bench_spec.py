"""BENCHMARK.json against the rules the benchmark keeps, and every cell's
files found by name."""
import ast
import glob
import os
import re

import pytest

from bench import harness

SPEC = harness.load_json(harness.ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRIC_KEYS = {"name", "unit", "better", "source"}


def _metrics():
    return SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024


def test_command_and_paths():
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(harness.ROOT, p))
    assert 1 <= len(SPEC["command"]) <= 32
    for word in SPEC["command"]:
        assert 1 <= len(word) <= 200 and "\n" not in word and "\t" not in word
        if word.endswith(".py"):
            assert any(word.startswith(p + "/") for p in SPEC["paths"])


def test_run_seconds_fits_a_full_check():
    rs = SPEC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("entry", SPEC["configs"] + SPEC["workloads"]
                         + SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda e: e["name"])
def test_names_and_text_fields(entry):
    assert NAME.match(entry["name"])
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200
            assert "\n" not in entry[key] and "\t" not in entry[key]
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])


def test_names_are_unique():
    for group in (SPEC["configs"], SPEC["workloads"], _metrics()):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("metric", _metrics(), ids=lambda m: m["name"])
def test_metric_fields(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    if metric in SPEC["end_to_end"]:
        assert set(metric) - {"workloads"} == METRIC_KEYS | {"bound"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert set(metric) - {"workloads"} == METRIC_KEYS | {"layer",
                                                              "moves"}
        assert metric["moves"] in {m["name"] for m in SPEC["end_to_end"]}
    for w in metric.get("workloads", []):
        assert w in {c["name"] for c in SPEC["workloads"]}


def test_layers_are_named_once_each():
    layers = {m["layer"] for m in SPEC["per_layer"]}
    perf = open(os.path.join(harness.ROOT, "PERF.md")).read()
    for layer in layers:
        assert f"| {layer} |" in perf, layer


def test_configs_resolve_and_are_used():
    used = {w["config"] for w in SPEC["workloads"]}
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for c in SPEC["configs"]:
        assert c["name"] in used
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["source"].startswith("https://")
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        config = harness.load_json(harness.ROOT, c["file"])
        assert config["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and key in config
        assert os.path.isfile(os.path.join(
            harness.BENCH, "entries", config["entry"] + ".py"))


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_files_resolve(cell):
    wl, config, traffic = harness.cell_files(SPEC, cell["name"])
    assert wl["chips"] in (1, 4)
    assert 1 <= len(wl["why"]) <= 200
    assert set(config["limits"])
    for trace in (False, True):
        for m in harness.cell_metrics(SPEC, cell["name"], trace):
            path = os.path.join(harness.BENCH, "metrics", m["name"])
            assert os.path.isfile(path + ".py") != os.path.isfile(
                path + ".json"), m["name"]
    assert traffic


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_reports_what_it_must(cell):
    e2e = {m["name"] for m in harness.cell_metrics(SPEC, cell["name"], False)}
    layer = harness.cell_metrics(SPEC, cell["name"], True)
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    for m in layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])


def test_four_chip_cells_at_most_half():
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_no_topology_described_while_importing():
    """A TPU topology may be described only inside a test's fixture, and
    nothing under the benchmark describes one at all."""
    files = glob.glob(os.path.join(harness.BENCH, "**", "*.py"),
                      recursive=True)
    files += glob.glob(os.path.join(os.path.dirname(__file__), "*.py"))
    for path in files:
        if path == __file__:
            continue
        tree = ast.parse(open(path).read())
        names = {n.attr if isinstance(n, ast.Attribute) else n.id
                 for n in ast.walk(tree)
                 if isinstance(n, (ast.Attribute, ast.Name))}
        assert "get_topology_desc" not in names, path


def test_metric_readers_are_silent_without_their_source():
    ctx = dict(entry="other", family="other", setup_s=1.0, window_s=1.0,
               units=1, requests=1, failed=0, setup_compile_s=0.0,
               setup_compiles=0, window_compiles=0, trace=None, split=None)
    for m in SPEC["per_layer"]:
        assert harness.read_metric(m, ctx) is None, m["name"]


def test_ignores_bench_run():
    for path in glob.glob(os.path.join(harness.BENCH, "**", "*.py"),
                          recursive=True):
        assert "BENCH_RUN" not in open(path).read(), path


def test_every_metric_reader_reads_its_own_entry(monkeypatch):
    """Each reader in bench/metrics gives a number for its own entry's
    family and nothing for another."""
    from repro.core import sweep
    monkeypatch.setattr(sweep, "_COMPILED_KEYS", {"m2", "m4"})
    trace = {"busy_s": [0.5, 0.7], "window_s": 2.0}
    for path in glob.glob(os.path.join(harness.BENCH, "metrics", "*.py")):
        name = os.path.basename(path)[:-3]
        values = {}
        for family in ("sim", "other"):
            ctx = dict(entry=family, family=family, setup_s=3.0,
                       window_s=2.0, units=2, requests=1000, failed=0,
                       setup_compile_s=1.5, setup_compiles=4,
                       window_compiles=0, trace=trace, split=None)
            values[family] = harness.read_metric({"name": name}, ctx)
        if name == "setup_s":
            assert values == {"sim": 3.0, "other": 3.0}
            continue
        assert values["other"] is None, name
        assert values["sim"] > 0, name
    sim_idle = harness.read_metric({"name": "sim.idle_share"},
                                   dict(family="sim", trace=trace))
    assert sim_idle == 100.0 * (1 - 0.6 / 2.0)


#: The scope and span entries this benchmark defines; a later PR may add
#: more, each with an entry and a data file alone.
SPLIT_METRICS = {
    "sim.l1_ns_per_req": {"scope": "l1"},
    "sim.l2_ns_per_req": {"scope": "l2"},
    "sim.fill_ns_per_req": {"scope": "fill"},
    "sim.timing_ns_per_req": {"scope": "timing"},
    "sim.probe_ns_per_req": {"scope": "probe"},
    "sim.noc_ns_per_req": {"scope": "noc"},
    "sim.idle_prepare_share": {"span": "sweep.prepare"},
    "sim.idle_inputs_share": {"span": "sweep.inputs"},
    "sim.idle_launch_share": {"span": "sweep.launch"},
    "sim.idle_fetch_share": {"span": "sweep.fetch"},
    "sim.idle_summarize_share": {"span": "sweep.summarize"}}


def _split_metrics(spec=SPEC):
    """(metric, its data file) of every per-layer entry read from the
    split."""
    out = []
    for m in spec["per_layer"]:
        path = os.path.join(harness.BENCH, "metrics", m["name"] + ".json")
        if os.path.isfile(path):
            out.append((m, harness.load_json(path)))
    return out


def _recorded_ctx(tmp_path, recorded, **kw):
    from bench import stages, tracing
    trace = tracing.load(tracing.find_xplane(recorded.dir(tmp_path)))
    ctx = dict(family="sim", failed=0, trace=tracing.reduce(trace, [0]),
               split=stages.split(trace, recorded.hlo(), [0], recorded.runs,
                                  1))
    ctx.update(kw)
    return ctx


def _recorded_value(desc, recorded):
    """What a scope or span entry reads on the recorded trace: the split
    the stage reduction gave it before it read scopes, per request of
    the unit's two runs or as a share of the window; None for a scope or
    span the recorded run does not name."""
    if desc.get("scope") in recorded.scopes_s:
        return 1e9 * recorded.scopes_s[desc["scope"]] / 1920
    if desc.get("span") in recorded.idle_s:
        return 100.0 * recorded.idle_s[desc["span"]] / recorded.window_s
    return None


def _check_split_entries(spec):
    """What holds for every scope or span entry, those a later PR adds
    too: one data file naming exactly one scope or one span, the
    entry's fixed fields, and the entries defined here among them."""
    found = _split_metrics(spec)
    for m, desc in found:
        assert set(desc) in ({"scope"}, {"span"}), m
        key, = desc
        assert isinstance(desc[key], str) and desc[key], m
        assert m["unit"] == ("ns/req" if key == "scope" else "%"), m
        assert m["source"] == "device_trace" and m["better"] == "lower"
        assert m.get("workloads"), m
    assert {m["name"]: d for m, d in found}.items() >= SPLIT_METRICS.items()


def _check_recorded_reads(spec, ctx, recorded):
    """Every scope or span entry reads the recorded trace's number where
    the recorded run names its scope or span, and nothing elsewhere;
    without a split, or where a request failed, a scope reads nothing."""
    for metric, desc in _split_metrics(spec):
        got = harness.read_metric(metric, ctx)
        want = _recorded_value(desc, recorded)
        if want is None:
            assert got is None, metric["name"]
        else:
            assert got == pytest.approx(want, rel=1e-9), metric["name"]
        assert harness.read_metric(metric, dict(ctx, split=None)) is None
        if "scope" in desc:
            assert harness.read_metric(metric, dict(ctx, failed=960)) is None


def test_split_metrics_name_a_scope_or_a_span():
    _check_split_entries(SPEC)


@pytest.mark.parametrize("name", sorted(SPLIT_METRICS))
def test_split_metric_reads_the_recorded_trace(name, tmp_path, recorded):
    """Each scope or span entry defined here reads the recorded trace's
    number: the split the stage reduction gave it before it read scopes.
    Without a split, or where a request failed, it reads nothing."""
    metric = {m["name"]: m for m in SPEC["per_layer"]}[name]
    ctx = _recorded_ctx(tmp_path, recorded)
    desc = SPLIT_METRICS[name]
    got = harness.read_metric(metric, ctx)
    assert got == pytest.approx(_recorded_value(desc, recorded), rel=1e-9)
    assert harness.read_metric(metric, dict(ctx, split=None)) is None
    if "scope" in desc:
        assert harness.read_metric(metric, dict(ctx, failed=960)) is None


def test_every_split_entry_reads_what_the_trace_names(tmp_path, recorded):
    _check_recorded_reads(SPEC, _recorded_ctx(tmp_path, recorded), recorded)


def test_a_later_scope_entry_needs_no_test_edit(tmp_path, monkeypatch,
                                               recorded):
    """A later PR adds a scope metric with a BENCHMARK.json entry and a
    data file alone (here a sector scope the recorded run does not
    name): the checks above hold for it unedited, and it reads nothing
    from a trace without its scope."""
    import copy
    import shutil
    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(harness.BENCH, "metrics"),
                    bench / "metrics")
    (bench / "metrics" / "sim.sector_ns_per_req.json").write_text(
        '{"scope": "sector"}\n')
    spec = copy.deepcopy(SPEC)
    spec["per_layer"].append({
        "name": "sim.sector_ns_per_req", "unit": "ns/req",
        "better": "lower", "source": "device_trace", "layer": "round loop",
        "moves": "sim_req_per_s", "workloads": ["sim_ata_hi_points"]})
    ctx = _recorded_ctx(tmp_path / "rec", recorded)
    monkeypatch.setattr(harness, "BENCH", str(bench))
    _check_split_entries(spec)
    _check_recorded_reads(spec, ctx, recorded)
    assert harness.read_metric(spec["per_layer"][-1], ctx) is None


def test_top_level_stages_sum_to_at_most_the_busy_time(tmp_path, recorded):
    ctx = _recorded_ctx(tmp_path, recorded)
    busy = harness.read_metric({"name": "sim.device_ns_per_req"},
                               dict(ctx, requests=1920))
    top = sum(harness.read_metric({"name": f"sim.{s}_ns_per_req"}, ctx)
              for s in ("l1", "l2", "fill", "noc", "timing"))
    assert 0.9 * busy < top <= busy


def test_misspelt_scope_reads_nothing(tmp_path, recorded, capsys):
    ctx = _recorded_ctx(tmp_path, recorded)
    for desc in ({"scope": "l3"}, {"span": "sweep.lauch"}):
        assert harness.read_split_metric(desc, ctx) is None
    err = capsys.readouterr().err
    assert "names the scope 'l3'" in err and "'sweep.lauch'" in err


class _SecondEntryCell:
    """A cell of a second entry module: its one unit is the two grid
    runs of the recorded trace, which its check finds correct."""
    run_requests = [960, 960]
    requests_per_unit = 1920

    def __init__(self, config, traffic, seed, traced=False):
        pass

    def unit(self):
        return "answer"

    def check(self, outputs, limits):
        return {"gap": (0.0, 0.0)}, 0


@pytest.mark.parametrize("family", ["sim", "other"])
def test_second_entry_of_a_family_reports_its_metrics(
        family, tmp_path, monkeypatch, recorded):
    """An entry module built from new files alone, of family ``sim`` and
    giving its program's HLO, reports the end-to-end metrics and every
    per-layer metric of a sim cell that its traced window (here the
    recorded trace) holds, the scope and span entries among them. One
    of another family, with no HLO, reports ``setup_s`` alone."""
    import sys
    import types
    from bench import tracing
    from repro.core import sweep
    mod = types.ModuleType("bench.entries.second")
    mod.FAMILY, mod.Cell = family, _SecondEntryCell
    if family == "sim":
        mod.compiled_hlo = recorded.hlo
    monkeypatch.setitem(sys.modules, "bench.entries.second", mod)
    monkeypatch.setattr(sweep, "_COMPILED_KEYS", {"m4"})
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "run"))
    xplane = tracing.find_xplane(recorded.dir(tmp_path / "rec"))
    monkeypatch.setattr(tracing, "find_xplane", lambda d: xplane)
    cell = "sim_ata_hi_points"
    files = ({"name": cell, "chips": 1}, {"entry": "second", "limits": {}},
             {})
    unnamed = {m["name"] for m, d in _split_metrics()
               if _recorded_value(d, recorded) is None}
    for trace in (False, True):
        result, _ = harness.run_cell(SPEC, cell, seed=1, seconds=0.0,
                                     trace=trace, t0=0.0,
                                     require_chip=False, files=files)
        want = {m["name"] for m in harness.cell_metrics(SPEC, cell, trace)}
        if family == "other":
            want = {"setup_s"} & want
        assert set(result["metrics"]) == want - unnamed
        assert result["correct"] and result["attempted"] == 1920
    l2 = result["metrics"].get("sim.l2_ns_per_req", {}).get("value")
    assert l2 == (None if family == "other" else pytest.approx(
        1e9 * recorded.scopes_s["l2"] / 1920, rel=1e-9))
