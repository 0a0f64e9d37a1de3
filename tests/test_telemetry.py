"""Observability stack: zero-cost-off, conservation, exact quantiles.

The telemetry contract has four legs, each tested here:

* **zero cost when off** — ``telemetry=None`` (the default) produces
  bit-identical results to the pre-telemetry code paths (the committed
  architecture goldens still hold with telemetry *on*, and turning it
  on/off never moves a counter), and the executable caches only grow
  when a telemetry config is actually passed;
* **conservation** — every windowed counter series sums exactly (no
  tolerance) to its ``SimResult`` / ``ServeResult`` total, across the
  policy zoo x NoC models and the serving policies x admission widths;
* **exact quantiles** — the serving latency histogram reproduces
  ``np.percentile`` over the materialized per-request latencies bit
  for bit (integral cost model), and the simulator's log2-bucketed
  variant is a conservative upper bound;
* **exporters** — Perfetto traces (generated and the committed smoke
  baseline) validate against the Chrome-trace-event schema, run
  manifests attach to all report kinds, and re-binned timelines are
  invariant to the capture window (hypothesis property below).
"""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro.core import PAPER_GEOMETRY, APPS, TelemetryConfig, make_trace
from repro.core import simulate
from repro.core.telemetry import (hist_quantile, log2_bucket,
                                  serving_hist_bins)
from repro.core.trace.serving import ServingMix
from repro.obs import ConservationError, validate_trace
from repro.obs.perfetto import trace_events, write_trace
from repro.serving import SERVING_POLICIES, ServingConfig, engine, \
    serve_stream

ROUNDS = 96          # divisible by the default window (32)
TEL = TelemetryConfig(window=32)


def _trace(app="cfd", rounds=ROUNDS):
    return make_trace(dataclasses.replace(APPS[app], rounds=rounds),
                      kernel=1)


@pytest.fixture(scope="module")
def stream():
    return ServingMix(("chat", "batch")).make_stream(
        n_shards=4, rounds=64, seed=1)


# ---------------------------------------------------------------------------
# zero cost when off: bit-exactness against the uninstrumented path
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("noc", ["ideal", "crossbar"])
def test_sim_result_identical_with_telemetry_on(noc):
    """The window restructuring preserves the per-round op sequence:
    every SimResult field is bit-equal with telemetry on vs off."""
    tr = _trace()
    base = simulate("ata", tr, noc=noc)
    res, tl = simulate("ata", tr, noc=noc, telemetry=TEL)
    assert base == res                      # NamedTuple: full compare
    assert tl.rounds == ROUNDS and tl.n_windows == ROUNDS // TEL.window


def test_sim_telemetry_on_still_matches_committed_golden():
    """Transitivity made explicit: the instrumented run reproduces the
    committed pre-refactor golden numbers, not just the current code."""
    from test_arch_registry import GOLDEN, INTEGRAL_FIELDS
    res, _ = simulate("ata", _trace("cfd", 192), telemetry=TelemetryConfig(window=64))
    for field, want in GOLDEN[("cfd", "ata")].items():
        got = getattr(res, field)
        if field in INTEGRAL_FIELDS:
            assert got == want, field
        else:
            assert got == pytest.approx(want, rel=1e-12), field


@pytest.mark.parametrize("b", [1, 4])
def test_serving_result_identical_with_telemetry_on(stream, b):
    base = serve_stream("ata", stream.batched(b))
    res, tl = serve_stream("ata", stream.batched(b), telemetry=TEL)
    assert base.local_hits == res.local_hits
    assert base.remote_hits == res.remote_hits
    assert base.recomputed_blocks == res.recomputed_blocks
    assert base.probe_messages == res.probe_messages
    assert base.cycles == res.cycles
    np.testing.assert_array_equal(base.latency, res.latency)
    np.testing.assert_array_equal(base.served, res.served)
    np.testing.assert_array_equal(base.shard_load, res.shard_load)
    assert base.lat_hist is None            # off: no histogram carry
    assert res.lat_hist is not None and res.hist_exact


def test_serving_off_path_compiles_nothing_new(stream):
    # a config no other test (or fig_serving_scale's default capture)
    # uses, so the cache-growth accounting below is unambiguous even
    # when the whole suite shares one process-wide executable cache
    tel = TelemetryConfig(window=16, sim_hist_bins=8)
    serve_stream("broadcast", stream)       # ensure cached
    before = engine.compile_count()
    serve_stream("broadcast", stream)
    assert engine.compile_count() == before  # same executable reused
    serve_stream("broadcast", stream, telemetry=tel)
    assert engine.compile_count() == before + 1  # telemetry keys anew
    serve_stream("broadcast", stream, telemetry=tel)
    assert engine.compile_count() == before + 1


def test_sweep_telemetry_keys_new_executable():
    from repro.core import sweep as sweep_engine
    from repro.core.sweep import SweepGrid, SweepPoint
    tr = _trace(rounds=64)
    grid = SweepGrid.from_points(
        [SweepPoint("ata", PAPER_GEOMETRY, tr, "ideal", "lax")])
    grid.run()
    before = sweep_engine.compile_count()
    run_off = grid.run()                    # cached: no new compile
    assert sweep_engine.compile_count() == before
    assert run_off.timelines is None
    run_on = grid.run(telemetry=TEL)
    assert sweep_engine.compile_count() == before + 1
    assert len(run_on.timelines) == 1
    run_on.timelines[0].check(run_on.results[0])
    assert run_on.results[0] == run_off.results[0]


# ---------------------------------------------------------------------------
# conservation: window sums == run totals, exactly
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("noc", ["ideal", "crossbar"])
@pytest.mark.parametrize("arch", ["private", "ata", "ciao"])
def test_sim_conservation(arch, noc):
    res, tl = simulate(arch, _trace(), noc=noc, telemetry=TEL)
    tl.check(res)                           # raises on any mismatch
    # spot-check the mechanism too: series deltas telescope to totals
    assert tl.series("requests").sum() == tl.total("requests")


@pytest.mark.parametrize("b", [1, 4])
@pytest.mark.parametrize("policy", SERVING_POLICIES)
def test_serving_conservation(stream, policy, b):
    res, tl = serve_stream(policy, stream.batched(b), telemetry=TEL)
    tl.check(res)
    assert tl.hist.sum() == res.served.sum()


def test_conservation_error_actually_raises(stream):
    res, tl = serve_stream("ata", stream, telemetry=TEL)
    broken = res._replace(probe_messages=res.probe_messages + 1)
    with pytest.raises(ConservationError):
        tl.check(broken)


# ---------------------------------------------------------------------------
# exact histogram quantiles
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("q", [0.0, 50.0, 90.0, 99.0, 99.9, 100.0])
@pytest.mark.parametrize("policy", SERVING_POLICIES)
def test_serving_histogram_percentile_is_exact(stream, policy, q):
    """hist_quantile over the value-resolved bincount reproduces
    np.percentile over the materialized latencies bit for bit."""
    res, _ = serve_stream(policy, stream, telemetry=TEL)
    assert res.hist_exact
    lat = res.request_latencies
    assert res.latency_percentile(q) == float(np.percentile(lat, q))


def test_serving_histogram_not_exact_under_fractional_costs(stream):
    """A non-integral cost model falls back to materialized
    percentiles rather than reading a mis-resolved histogram."""
    cfg = ServingConfig(noc="ring")
    res, _ = serve_stream("ata", stream, cfg, telemetry=TEL)
    assert not res.hist_exact
    lat = res.request_latencies
    assert res.latency_percentile(99) == float(np.percentile(lat, 99))


def test_hist_quantile_against_numpy_randomized():
    rng = np.random.default_rng(7)
    values = rng.integers(0, 50, size=500)
    counts = np.bincount(values, minlength=60)
    for q in (0, 1, 25, 50, 75, 90, 99, 99.9, 100):
        assert hist_quantile(counts, q) \
            == float(np.percentile(values, q))


def test_sim_log2_percentile_is_conservative():
    res, tl = simulate("ata", _trace(), telemetry=TEL)
    p99 = tl.hist_percentile(99)
    # bucket upper edge: a power of two and >= the mean latency
    assert p99 == 2.0 ** round(np.log2(p99))
    assert p99 >= res.l1_latency


def test_log2_bucket_edges():
    got = np.asarray(log2_bucket(
        np.asarray([0.0, 1.0, 1.5, 2.0, 3.9, 4.0, 1e12]), 5))
    np.testing.assert_array_equal(got, [0, 0, 0, 1, 1, 2, 4])


def test_serving_hist_bins_covers_max_latency():
    assert serving_hist_bins(720.0) == 722
    assert serving_hist_bins(720.5) == 723


# ---------------------------------------------------------------------------
# exporters: Perfetto traces + run manifests
# ---------------------------------------------------------------------------
def test_sim_trace_validates_and_has_all_track_kinds(tmp_path):
    res, tl = simulate("ata", _trace(), noc="crossbar", telemetry=TEL)
    obj = trace_events(tl)
    validate_trace(obj)
    phs = {e["ph"] for e in obj["traceEvents"]}
    assert phs == {"M", "X", "C"}           # metadata, spans, counters
    path = tmp_path / "sim_trace.json"
    write_trace(str(path), tl)
    validate_trace(json.loads(path.read_text()))


def test_serve_trace_validates(stream, tmp_path):
    _, tl = serve_stream("ata", stream, telemetry=TEL)
    path = tmp_path / "serve_trace.json"
    write_trace(str(path), tl)
    obj = json.loads(path.read_text())
    validate_trace(obj)
    assert any(e["ph"] == "C" for e in obj["traceEvents"])


def test_committed_smoke_trace_is_valid():
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "baselines", "telemetry_smoke_trace.json")
    validate_trace(json.loads(open(path).read()))


def test_validate_trace_rejects_malformed():
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": []})
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [
            {"ph": "X", "name": "n", "pid": 1}]})  # no ts/dur/tid


def test_run_manifest_shape():
    from repro.obs.manifest import run_manifest
    m = run_manifest(phases={"x": 1.25}, extra={"note": "t"})
    assert isinstance(m["git_sha"], str) and len(m["git_sha"]) == 40
    assert m["jax_version"] and m["backend"]
    import jax
    dev = jax.devices()[0]
    assert (m["device_platform"], m["device_kind"], m["device_count"]) \
        == (dev.platform, dev.device_kind, len(jax.devices()))
    assert m["phases_wall_s"] == {"x": 1.25}
    assert m["note"] == "t"
    assert "sweep" in m["compile_counts"]
    json.dumps(m)                           # must be JSON-serializable


def test_sensitivity_report_carries_manifest():
    from repro.core import report as sensitivity
    rep = sensitivity.run_sensitivity(
        app="cfd", archs=("ata",), knobs={"hide": (5.0,)},
        kernels_per_app=1, rounds=64)
    assert rep["manifest"]["git_sha"]
    assert "sweep" in rep["manifest"]["phases_wall_s"]


def test_serving_scale_report_carries_manifest_and_exact_quantiles():
    from benchmarks import fig_serving_scale
    rep = fig_serving_scale.run(
        rounds=64, shards=(4,),
        mixes=(ServingMix(("chat", "rag"), name="chat+rag"),),
        policies=("ata",), slot_counts=(1,), reps=1)
    assert rep["manifest"]["git_sha"]
    assert all(c["hist_exact"] for c in rep["cells"])


def test_telemetry_capture_writes_everything(tmp_path):
    from benchmarks import telemetry_capture
    out = tmp_path / "cap"
    rep = telemetry_capture.capture(str(out), rounds=64)
    for name in ("sim_timeline.json", "sim_timeline.csv",
                 "sim_trace.json", "serve_timeline.json",
                 "serve_timeline.csv", "serve_trace.json",
                 "manifest.json", "telemetry_report.json"):
        assert (out / name).exists(), name
    assert rep["kind"] == "telemetry"
    assert rep["serving"]["hist_exact"]
    validate_trace(json.loads((out / "serve_trace.json").read_text()))


# ---------------------------------------------------------------------------
# window invariance: rebin(k) == capture at k*W (exactly)
# ---------------------------------------------------------------------------
def test_rebin_matches_coarser_capture(stream):
    _, fine = serve_stream("ata", stream, telemetry=TelemetryConfig(
        window=16))
    _, coarse = serve_stream("ata", stream, telemetry=TelemetryConfig(
        window=32))
    rebinned = fine.rebin(2)
    assert rebinned.window == coarse.window
    for name in coarse.counter_names:
        np.testing.assert_array_equal(rebinned.cumulative[name],
                                      coarse.cumulative[name], err_msg=name)


def test_window_must_divide_run_length():
    with pytest.raises(ValueError, match="nearest divisor"):
        simulate("ata", _trace(rounds=96),
                 telemetry=TelemetryConfig(window=17))


def test_window_invariance_property(stream):
    """Hypothesis form of the rebin contract: for any divisor pair
    (w1 | w2), a capture at w1 re-binned to w2 equals the capture taken
    at w2 — cumulative snapshots at shared boundaries are identical
    regardless of stride."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st
    windows = (4, 8, 16, 32)
    captures = {w: serve_stream("ata", stream,
                                telemetry=TelemetryConfig(window=w))[1]
                for w in windows}

    @settings(max_examples=16, deadline=None)
    @given(st.sampled_from(windows), st.sampled_from(windows))
    def prop(w1, w2):
        if w2 % w1:
            return
        rebinned = captures[w1].rebin(w2 // w1)
        coarse = captures[w2]
        assert rebinned.window == coarse.window
        for name in coarse.counter_names:
            np.testing.assert_array_equal(
                rebinned.cumulative[name], coarse.cumulative[name],
                err_msg=f"{name} @ {w1}->{w2}")

    prop()
