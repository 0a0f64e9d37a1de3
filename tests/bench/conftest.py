"""The benchmark's tests import ``bench`` from the repository root."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import copy  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import types  # noqa: E402

import pytest  # noqa: E402


def tiny_files(name):
    """(workload, configuration, traffic) of cell ``name`` cut to a size
    a CPU test holds: 96 simulated rounds of two apps. Shapes and code
    paths stay the cell's own."""
    from bench import harness
    spec = harness.load_json(harness.ROOT, "BENCHMARK.json")
    wl, config, traffic = harness.cell_files(spec, name)
    config = copy.deepcopy(config)
    config["app_defaults"]["rounds"] = 96
    traffic = dict(traffic, apps=["b+tree", "SN"])
    return wl, config, traffic


@pytest.fixture
def tiny():
    return tiny_files


def _recorded_hlo():
    from bench import harness
    path = os.path.join(harness.BENCH, "testdata", "stages.hlo.json.gz")
    with gzip.open(path, "rt") as f:
        return [tuple(x) for x in json.load(f)]


def _recorded_dir(path):
    """A profiler log directory under ``path`` holding the recorded
    trace, as a traced run leaves it."""
    from bench import harness
    dest = path / "plugins" / "profile" / "t"
    dest.mkdir(parents=True)
    with gzip.open(os.path.join(harness.BENCH, "testdata",
                                "stages.xplane.pb.gz")) as f:
        (dest / "vm.xplane.pb").write_bytes(f.read())
    return str(path)


@pytest.fixture
def recorded():
    """The small trace ``bench/testdata/record_stage_trace.py`` recorded
    on a TPU v5e: one unit of two single-point grid runs (``private``,
    then ``ata``; SN kernel 0 at 8 rounds, 30 cores, m = 4, so 960
    requests each) under the benchmark's spans, with the HLO of both
    executables. ``dir(path)`` unpacks it under ``path``. ``scopes_s``
    and ``idle_s`` are the split the stage reduction gave it before it
    read scopes (seconds; ``l1`` is its ``l1`` plus its ``probe``), over
    both runs and the 43.34074 ms window."""
    return types.SimpleNamespace(
        hlo=_recorded_hlo, dir=_recorded_dir, runs=[960, 960],
        window_s=0.04334074,
        scopes_s={"l1": 0.000342979 + 0.000422858, "probe": 0.000422858,
                  "l2": 0.000874414, "fill": 0.000701981,
                  "noc": 2.3344e-05, "timing": 0.000163445},
        idle_s={"sweep.prepare": 0.01520296, "sweep.inputs": 0.015770959,
                "sweep.launch": 0.002720309, "sweep.fetch": 0.005757628,
                "sweep.summarize": 0.00047875})


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    """Runs driven here leave JAX's process-wide cache settings as they
    found them: the persistent cache stays off, and the harness's
    compile-time threshold is put back."""
    import jax
    import repro.compile_cache
    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache",
                        lambda root: "off in tests")
    prev = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev)
