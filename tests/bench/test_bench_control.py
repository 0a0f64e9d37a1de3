"""The control comes out not correct, and the program correct, at a
size a test holds: the plain reference with its timing in bfloat16 (the
precision below the configuration's float32), put in the program's
place, fails at least one limit on every seed."""
import pytest

from bench import harness
from bench.control import readings

SPEC = harness.load_json(harness.ROOT, "BENCHMARK.json")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
@pytest.mark.parametrize("seed", [1, 2**31 + 1])
def test_control_fails_and_program_passes(cell, seed, tiny):
    wl, config, traffic = tiny(cell)
    limits = config["limits"]
    r = readings(config, traffic, seed, control=True)
    assert all(r["program"][k] <= limits[k] for k in limits), r
    assert any(r["control"][k] > limits[k] for k in limits), r
