"""The benchmark's tests import ``bench`` from the repository root."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import copy  # noqa: E402

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
