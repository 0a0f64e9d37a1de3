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
            assert os.path.isfile(os.path.join(
                harness.BENCH, "metrics", m["name"] + ".py"))
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
    ctx = dict(entry="other", setup_s=1.0, window_s=1.0, units=1,
               requests=1, setup_compile_s=0.0, setup_compiles=0,
               window_compiles=0, trace=None)
    for m in SPEC["per_layer"]:
        assert harness.read_metric(m, ctx) is None, m["name"]


def test_ignores_bench_run():
    for path in glob.glob(os.path.join(harness.BENCH, "**", "*.py"),
                          recursive=True):
        assert "BENCH_RUN" not in open(path).read(), path


def test_every_metric_reader_reads_its_own_entry(monkeypatch):
    """Each reader in bench/metrics gives a number for its own entry and
    nothing for another."""
    from repro.core import sweep
    monkeypatch.setattr(sweep, "_COMPILED_KEYS", {"m2", "m4"})
    trace = {"busy_s": [0.5, 0.7], "window_s": 2.0}
    for path in glob.glob(os.path.join(harness.BENCH, "metrics", "*.py")):
        name = os.path.basename(path)[:-3]
        values = {}
        for entry in ("sweep", "other"):
            ctx = dict(entry=entry, setup_s=3.0, window_s=2.0, units=2,
                       requests=1000, setup_compile_s=1.5, setup_compiles=4,
                       window_compiles=0, trace=trace)
            values[entry] = harness.read_metric({"name": name}, ctx)
        if name == "setup_s":
            assert values == {"sweep": 3.0, "other": 3.0}
            continue
        assert values["other"] is None, name
        assert values["sweep"] > 0, name
    sim_idle = harness.read_metric({"name": "sim.idle_share"},
                                   dict(entry="sweep", trace=trace))
    assert sim_idle == 100.0 * (1 - 0.6 / 2.0)
