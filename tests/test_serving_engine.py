"""Vectorized serving engine vs the retained numpy oracle.

The contract is *bit-exactness*: ``repro.serving.engine.serve_stream``
must reproduce the oracle's hit/probe/fetch accounting integer-for-
integer on the same :class:`~repro.core.trace.serving.RequestStream`,
for every serving policy, both on packed multi-request rounds and on
the sequentialized stream (one request per round — where round
semantics degenerate to the oracle's original one-at-a-time order) —
and at every batched admission width ``B`` (slots replay as sequential
sub-rounds, so counters never move with ``B``).
On top of that: conservation invariants, probe-message bounds, probe-
backend equivalence, NoC pricing conservation, per-tenant attribution,
overflow-headroom accumulation, compile-count bounds (one executable
per policy x backend x B), the committed serving baseline, and the
``compare_serving`` regression gate with its batched-speedup floor.
"""
import numpy as np
import pytest

from repro.core.trace.serving import ServingMix, tenant_stream
from repro.serving import (SERVING_POLICIES, ServingConfig, engine, ref,
                           serve_stream)

N_SHARDS = 4
ROUNDS = 64


@pytest.fixture(scope="module")
def stream():
    # chat+batch: high- and low-sharing tenants with bursty arrivals,
    # past the cold-start transient at 4 shards x 64 rounds
    return ServingMix(("chat", "batch")).make_stream(
        n_shards=N_SHARDS, rounds=ROUNDS, seed=1)


@pytest.fixture(scope="module")
def results(stream):
    return {p: serve_stream(p, stream) for p in SERVING_POLICIES}


@pytest.fixture(scope="module")
def oracle(stream):
    return {p: ref.run_stream(p, ref.AtaCacheConfig(), stream)
            for p in SERVING_POLICIES}


def _assert_matches(res, st):
    assert res.local_hits == st.local_hits
    assert res.remote_hits == st.remote_hits
    assert res.recomputed_blocks == st.recomputed_blocks
    assert res.probe_messages == st.probe_messages
    assert res.remote_fetch_blocks == st.remote_fetch_blocks
    assert res.directory_sync_entries == st.directory_sync_entries
    np.testing.assert_array_equal(res.shard_load, st.shard_load)


# ---------------------------------------------------------------------------
# oracle equivalence
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", SERVING_POLICIES)
def test_engine_matches_oracle_packed(results, oracle, policy):
    """Full rounds (up to one request per shard) — bit-exact."""
    _assert_matches(results[policy], oracle[policy])


@pytest.mark.parametrize("policy", SERVING_POLICIES)
def test_engine_matches_oracle_sequential(stream, policy):
    """One request per round: the oracle's original sequential order."""
    seq = stream.sequential()
    res = serve_stream(policy, seq)
    st = ref.run_stream(policy, ref.AtaCacheConfig(), seq)
    _assert_matches(res, st)
    # and sequentialization preserves the request population exactly
    assert seq.n_requests == stream.n_requests


def test_oracle_broadcast_is_legacy_remote(stream):
    """`broadcast` is the legacy oracle's `remote` policy by alias."""
    a = ref.run_stream("broadcast", ref.AtaCacheConfig(), stream)
    b = ref.run_stream("remote", ref.AtaCacheConfig(), stream)
    assert (a.local_hits, a.remote_hits, a.probe_messages) \
        == (b.local_hits, b.remote_hits, b.probe_messages)


def test_oracle_rejects_engineless_policies(stream):
    with pytest.raises(ValueError):
        ref.run_stream("decoupled", ref.AtaCacheConfig(), stream)
    with pytest.raises(ValueError):
        serve_stream("decoupled", stream)


# ---------------------------------------------------------------------------
# conservation + bounds
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", SERVING_POLICIES)
def test_block_conservation(stream, results, policy):
    """Every valid block is served exactly once: hit or recomputed."""
    res = results[policy]
    total_blocks = int(stream.n_blocks[stream.valid].sum())
    assert (res.local_hits + res.remote_hits + res.recomputed_blocks
            == total_blocks)
    assert res.n_requests == stream.n_requests
    assert int(res.served.sum()) == stream.n_requests


def test_probe_message_bounds(stream, results):
    """private/ata never probe; broadcast probes <= blocks x (C-1)."""
    assert results["private"].probe_messages == 0
    assert results["ata"].probe_messages == 0
    total_blocks = int(stream.n_blocks[stream.valid].sum())
    bcast = results["broadcast"].probe_messages
    assert 0 < bcast <= total_blocks * (N_SHARDS - 1)


def test_ata_replicates_and_syncs(results):
    """ata fetches remotely and fills locally (Fig 7a); every newly
    sealed block is a directory delta all-gather entry; broadcast
    probes instead of syncing."""
    ata = results["ata"]
    assert ata.remote_fetch_blocks > 0
    assert ata.directory_sync_entries == ata.recomputed_blocks
    assert results["broadcast"].directory_sync_entries == 0
    assert results["private"].remote_fetch_blocks == 0


def test_hit_rate_ordering(results):
    """Sharing beats private; zero-cost visibility beats probing."""
    assert results["ata"].hit_rate >= results["broadcast"].hit_rate - 1e-9
    assert results["broadcast"].hit_rate > results["private"].hit_rate


# ---------------------------------------------------------------------------
# batched admission (slots = B)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ("lax", "pallas_interpret"))
@pytest.mark.parametrize("policy", SERVING_POLICIES)
def test_engine_matches_oracle_batched(stream, oracle, policy, backend):
    """Every policy x backend x B in {1,2,4} is oracle-exact.

    The oracle sequentializes slots by construction (row order is slot
    order), so one oracle run is the reference for every ``B``.
    """
    cfg = ServingConfig(probe_backend=backend)
    for b in (1, 2, 4):
        _assert_matches(serve_stream(policy, stream.batched(b), cfg),
                        oracle[policy])


def test_batched_equals_slot_sequential_outputs(stream, results):
    """B=4 reproduces the B=1 replay output-for-output — latency grid,
    tenant attribution, shard load — while the throughput model
    charges one round per B admissions (the batching win)."""
    r1 = results["ata"]
    r4 = serve_stream("ata", stream.batched(4))
    assert r4.slots == 4 and r1.slots == 1
    np.testing.assert_array_equal(r4.latency, r1.latency)
    np.testing.assert_array_equal(r4.served, r1.served)
    np.testing.assert_array_equal(r4.shard_load, r1.shard_load)
    np.testing.assert_array_equal(r4.tenant_requests,
                                  r1.tenant_requests)
    np.testing.assert_array_equal(r4.tenant_hit_blocks,
                                  r1.tenant_hit_blocks)
    np.testing.assert_array_equal(r4.tenant_latency_sum,
                                  r1.tenant_latency_sum)
    # fewer, wider rounds: strictly fewer modeled cycles, higher
    # modeled throughput — the >= 1.5x acceptance bar at B=4
    assert r4.cycles < r1.cycles
    assert r4.requests_per_kcycle >= 1.5 * r1.requests_per_kcycle


def test_batched_stream_api():
    mix = ServingMix(("chat", "batch"))
    st = mix.make_stream(n_shards=4, rounds=32, seed=2)
    b = st.batched(4)
    assert b.slots == 4
    assert b.rounds == 32 and b.admission_rounds == 8
    np.testing.assert_array_equal(b.hashes, st.hashes)   # relabeling
    back = b.slot_sequential()
    assert back.slots == 1 and back.admission_rounds == 32
    with pytest.raises(ValueError):
        st.batched(5)        # 32 rows not divisible by 5
    with pytest.raises(ValueError):
        st.batched(0)
    with pytest.raises(ValueError):
        mix.make_stream(n_shards=4, rounds=32, seed=2, slots=99)


def test_make_stream_slots_widen_admission():
    """slots=B admits the B=1 winners in slot 0 plus the contenders a
    one-slot grid would have dropped; offered traffic is unchanged."""
    mix = ServingMix(("chat", "batch"))
    st1 = mix.make_stream(n_shards=4, rounds=48, seed=3)
    st2 = mix.make_stream(n_shards=4, rounds=48, seed=3, slots=2)
    assert st2.slots == 2 and st2.rounds == 96
    assert st2.admission_rounds == st1.rounds
    # slot 0 of every round is exactly the rotating-priority winner
    v2 = st2.valid.reshape(48, 2, 4)
    h2 = st2.hashes.reshape(48, 2, 4, -1)
    np.testing.assert_array_equal(v2[:, 0], st1.valid)
    np.testing.assert_array_equal(h2[:, 0], st1.hashes)
    # wider admission serves the dropped contenders too
    assert st2.n_requests > st1.n_requests
    # slots beyond the contender count stay empty (2 tenants, B=4)
    st4 = mix.make_stream(n_shards=4, rounds=48, seed=3, slots=4)
    assert st4.n_requests == st2.n_requests
    assert not st4.valid.reshape(48, 4, 4)[:, 2:].any()


def test_b1_matches_committed_baseline():
    """The engine reproduces the committed serving baseline's B=1 cell
    integer-for-integer (guards the packed-directory rewrite)."""
    import json
    import pathlib
    path = pathlib.Path(__file__).resolve().parents[1] \
        / "benchmarks" / "baselines" / "serving_rounds512.json"
    rep = json.loads(path.read_text())
    cell = next(c for c in rep["cells"]
                if (c["shards"], c["mix"], c["policy"],
                    c.get("slots", 1)) == (8, "chat+rag", "ata", 1))
    mix = ServingMix(("chat", "rag"), name="chat+rag")
    st = mix.make_stream(n_shards=8, rounds=cell["rounds"],
                         seed=rep["config"]["seed"])
    res = serve_stream("ata", st)
    assert st.n_requests == cell["requests"]
    assert res.local_hits == cell["local_hits"]
    assert res.remote_hits == cell["remote_hits"]
    assert res.recomputed_blocks == cell["recomputed_blocks"]
    assert res.probe_messages == cell["probe_messages"]
    assert res.hit_rate == pytest.approx(cell["hit_rate"], rel=1e-12)


# ---------------------------------------------------------------------------
# probe backends
# ---------------------------------------------------------------------------
def test_pallas_interpret_backend_matches_lax(stream, results):
    cfg = ServingConfig(probe_backend="pallas_interpret")
    res = serve_stream("ata", stream, cfg)
    _assert_matches(res, ref.run_stream("ata", ref.AtaCacheConfig(),
                                        stream))
    np.testing.assert_array_equal(res.latency, results["ata"].latency)


def test_compiled_pallas_backend_raises_off_tpu():
    """``pallas`` means the Mosaic-compiled kernel: off-TPU the config
    is refused instead of silently interpreting."""
    import jax
    assert jax.default_backend() != "tpu"
    with pytest.raises(RuntimeError, match="needs a TPU"):
        ServingConfig(probe_backend="pallas")
    ServingConfig(probe_backend="pallas_interpret")


def test_bad_probe_backend_rejected():
    with pytest.raises(ValueError):
        ServingConfig(probe_backend="mosaic?")


# ---------------------------------------------------------------------------
# NoC pricing
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("noc", ("ideal", "crossbar", "ring"))
def test_noc_conservation_and_counter_stability(stream, results, noc):
    """Flit conservation holds per model, and pricing never perturbs
    the integer accounting (latency-only coupling)."""
    res = serve_stream("ata", stream, ServingConfig(noc=noc))
    assert res.noc_injected == pytest.approx(
        res.noc_delivered + res.noc_queued)
    assert res.noc_injected > 0          # remote fetches really priced
    _assert_matches(res, ref.run_stream("ata", ref.AtaCacheConfig(),
                                        stream))
    np.testing.assert_array_equal(res.served, results["ata"].served)


def test_ring_costs_more_latency_than_ideal(stream):
    """Hop distance adds delay on every remote fetch, so total modeled
    latency is strictly larger whenever remote traffic exists."""
    ideal = serve_stream("ata", stream, ServingConfig(noc="ideal"))
    ring = serve_stream("ata", stream, ServingConfig(noc="ring"))
    assert ideal.remote_fetch_blocks > 0
    assert float(ring.latency.sum()) > float(ideal.latency.sum())


# ---------------------------------------------------------------------------
# per-tenant attribution
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", SERVING_POLICIES)
def test_tenant_attribution_sums_to_totals(stream, results, policy):
    res = results[policy]
    assert res.tenants == stream.tenants
    assert int(res.tenant_requests.sum()) == stream.n_requests
    assert int(res.tenant_blocks.sum()) \
        == int(stream.n_blocks[stream.valid].sum())
    assert int(res.tenant_hit_blocks.sum()) \
        == res.local_hits + res.remote_hits
    assert float(res.tenant_latency_sum.sum()) \
        == pytest.approx(float(res.latency.sum()), rel=1e-5)


def test_chat_outhits_batch_under_ata(results):
    """The high-sharing tenant reuses more of its blocks."""
    res = results["ata"]
    chat, batch = (res.tenant_hit_blocks / np.maximum(res.tenant_blocks,
                                                      1))
    assert chat > batch


# ---------------------------------------------------------------------------
# stream generator
# ---------------------------------------------------------------------------
def test_tenant_slots_are_hash_disjoint():
    """Slot striding keeps tenants in disjoint hash sub-spaces."""
    a = tenant_stream("chat", n_shards=4, rounds=32, seed=7, slot=0)
    b = tenant_stream("chat", n_shards=4, rounds=32, seed=7, slot=1)
    ha = set(np.unique(a.hashes[a.valid])) - {0}
    hb = set(np.unique(b.hashes[b.valid])) - {0}
    assert ha and hb and not (ha & hb)


def test_one_tenant_mix_is_the_solo_stream():
    """Deterministic twin of the hypothesis property: a 1-tenant mix
    carries exactly the solo tenant's arrays (slot 0, no offset)."""
    solo = tenant_stream("rag", n_shards=4, rounds=48, seed=5, slot=0)
    mix = ServingMix(("rag",)).make_stream(n_shards=4, rounds=48, seed=5)
    np.testing.assert_array_equal(mix.valid, solo.valid)
    np.testing.assert_array_equal(mix.hashes, solo.hashes)
    np.testing.assert_array_equal(mix.n_blocks, solo.n_blocks)


def test_burst_and_diurnal_modulate_arrivals():
    """batch's bursts push arrivals above its base rate in some rounds;
    rag's diurnal swing makes round occupancy non-uniform."""
    batch = tenant_stream("batch", n_shards=8, rounds=512, seed=0)
    from repro.core.trace.serving import TENANTS
    base = TENANTS["batch"].rate
    # bursts multiply the arrival rate for whole windows, so mean
    # occupancy sits well above the base rate a burst-free stream
    # would fluctuate around
    assert batch.valid.mean() > base + 0.1
    rag = tenant_stream("rag", n_shards=8, rounds=4096, seed=0)
    half = rag.valid.sum() // 2
    first = rag.valid[:2048].sum()
    assert abs(int(first) - int(half)) > 64   # phase asymmetry


# ---------------------------------------------------------------------------
# overflow headroom
# ---------------------------------------------------------------------------
def test_near_overflow_latency_accumulation(stream):
    """Planted near-overflow run: with a recompute cost of 2^20 cycles
    the latency sums blow far past int32/f32-carry range; the host
    float64/int64 accumulators must stay exact to the integer."""
    cfg = ServingConfig(lat_recompute=float(1 << 20))
    res = serve_stream("private", stream, cfg)
    # the plant is real: past 2^31 (and past exact-f32 at 2^24)
    total = res.local_hits + 4 * res.remote_hits \
        + (1 << 20) * res.recomputed_blocks
    assert total > 2 ** 31
    # private + ideal NoC: latency is a pure integer cost model, so
    # the per-tenant sums and the latency grid agree exactly
    assert int(res.tenant_latency_sum.sum()) == total
    assert int(np.sum(res.latency, dtype=np.float64)) == total
    assert res.tenant_latency_sum.dtype == np.float64
    assert res.cycles == float(np.sum(
        res.latency.max(axis=1), dtype=np.float64))


def test_headroom_guard_rejects_unsafe_costs(stream):
    """Config-time guard: per-request latency beyond f32 integer-exact
    range is refused instead of silently losing cycles."""
    with pytest.raises(ValueError, match="f32"):
        serve_stream("private", stream,
                     ServingConfig(lat_recompute=2.0 ** 24))


# ---------------------------------------------------------------------------
# compile budget
# ---------------------------------------------------------------------------
def test_one_executable_per_policy(stream):
    """The chunked replay compiles once per (policy, stream geometry,
    config) and reuses it across calls."""
    before = engine.compile_count()
    small = ServingMix(("chat",)).make_stream(n_shards=2, rounds=16)
    for _ in range(3):
        for p in SERVING_POLICIES:
            serve_stream(p, small)
    assert engine.compile_count() - before <= len(SERVING_POLICIES)


def test_one_executable_per_policy_backend_slots():
    """The executable cache keys on (policy, backend, B): replaying at
    several widths and round counts compiles exactly one chunk per
    key — the benchmark grid's compile budget."""
    mix = ServingMix(("chat", "batch"))
    streams = [mix.make_stream(n_shards=2, rounds=r, seed=9)
               for r in (16, 32)]      # different rounds, same chunk
    before = engine.compile_count()
    for _ in range(2):
        for st in streams:
            for p in SERVING_POLICIES:
                for b in (1, 2, 4):
                    serve_stream(p, st.batched(b))
    assert engine.compile_count() - before \
        <= len(SERVING_POLICIES) * 3


# ---------------------------------------------------------------------------
# the regression gate
# ---------------------------------------------------------------------------
def _serving_report(**over):
    cell = {"shards": 4, "mix": "chat+batch", "policy": "ata",
            "requests": 1000, "hit_rate": 0.4, "probe_messages": 0,
            "p99_latency": 500.0}
    cell.update(over)
    return {"kind": "serving", "schema": 1,
            "config": {"shards": [4], "rounds": 64},
            "cells": [cell], "headline": {}}


def test_compare_serving_identity_and_drift():
    from repro.core.report import compare_serving
    base = _serving_report()
    assert compare_serving(base, base) == []
    # probe messages gate exactly — off by one fails
    fails = compare_serving(base, _serving_report(probe_messages=1))
    assert any("probe-message" in f for f in fails)
    # hit rate within tolerance passes, beyond fails (both directions)
    assert compare_serving(base,
                           _serving_report(hit_rate=0.4001)) == []
    fails = compare_serving(base, _serving_report(hit_rate=0.45))
    assert any("hit-rate" in f for f in fails)
    # request-count drift means the stream itself changed
    fails = compare_serving(base, _serving_report(requests=999))
    assert any("request count" in f for f in fails)


def test_compare_serving_structural_failures():
    from repro.core.report import compare_serving
    base = _serving_report()
    missing = dict(base, cells=[])
    assert any("missing" in f for f in compare_serving(base, missing))
    other_cfg = dict(base, config={"shards": [8], "rounds": 64})
    assert any("config mismatch" in f
               for f in compare_serving(base, other_cfg))
    not_serving = dict(base, kind="simspeed")
    assert any("not a serving report" in f
               for f in compare_serving(base, not_serving))
    # p99 is gated only on opt-in
    moved = _serving_report(p99_latency=900.0)
    assert compare_serving(base, moved) == []
    fails = compare_serving(base, moved, latency_rtol=0.25)
    assert any("p99" in f for f in fails)


def _batched_report(model=3.4, wall=0.9, slots=4):
    rep = _serving_report()
    rep["headline"] = {"batched_model_speedup": model,
                       "batched_wall_speedup": wall,
                       "batched_slots": slots}
    return rep


def test_compare_serving_batched_speedup_gate():
    """The batched modeled-throughput ratio gates one-sided against
    the 1.5x absolute floor and the baseline minus batched_rtol."""
    from repro.core.report import compare_serving
    base = _batched_report(model=3.4)
    assert compare_serving(base, _batched_report(model=3.2)) == []
    assert compare_serving(base, _batched_report(model=9.9)) == []
    # relative drop beyond tolerance fails even above the floor
    fails = compare_serving(base, _batched_report(model=2.0))
    assert any("batched modeled speedup" in f for f in fails)
    # the absolute floor binds even when the baseline sits near it
    low = _batched_report(model=1.55)
    fails = compare_serving(low, _batched_report(model=1.45))
    assert any("batched modeled speedup" in f for f in fails)
    # a candidate that lost the headline entirely fails
    gone = _serving_report()
    fails = compare_serving(base, gone)
    assert any("missing" in f for f in fails)
    # wall-clock ratio gates only on opt-in (host-dependent)
    slow_wall = _batched_report(model=3.4, wall=0.4)
    assert compare_serving(base, slow_wall) == []
    fails = compare_serving(base, slow_wall, wall_rtol=0.25)
    assert any("wall speedup" in f for f in fails)
    # a baseline without the headline (schema 1) never gates it
    assert compare_serving(_serving_report(), gone) == []


def test_compare_serving_per_slot_cells():
    """Cells key on slots too; schema-1 cells default to B=1."""
    from repro.core.report import compare_serving
    b1 = _serving_report()                   # no "slots" key
    b1_explicit = _serving_report(slots=1)
    assert compare_serving(b1, b1_explicit) == []
    # a B=4 baseline cell must find its B=4 twin, not the B=1 cell
    base = dict(b1, cells=[_serving_report()["cells"][0],
                           _serving_report(slots=4)["cells"][0]])
    cand_missing = dict(b1, cells=[_serving_report()["cells"][0]])
    fails = compare_serving(base, cand_missing)
    assert any("missing" in f and "4" in f for f in fails)


def test_fig_serving_scale_report_shape(tmp_path):
    """The benchmark emits a gate-compatible kind=serving report with
    per-B cells and the batched-speedup headline."""
    from benchmarks import fig_serving_scale
    from repro.core.report import compare_serving
    mix = ServingMix(("chat", "batch"))
    out = tmp_path / "serving.json"
    rep = fig_serving_scale.run(rounds=ROUNDS, shards=(N_SHARDS,),
                                mixes=(mix,), seed=1,
                                out_json=str(out))
    assert out.exists()
    assert rep["kind"] == "serving"
    assert len(rep["cells"]) == len(SERVING_POLICIES) \
        * len(fig_serving_scale.SLOT_COUNTS)
    assert compare_serving(rep, rep) == []
    assert rep["headline"]["probes_filtered"] > 0
    assert rep["headline"]["batched_model_speedup"] >= 1.5
    # per-B cells share every counter (slot-order exactness) and the
    # B=1 cells reproduce the module fixtures
    by_key = {(c["policy"], c["slots"]): c for c in rep["cells"]}
    assert by_key[("ata", 1)]["probe_messages"] == 0
    assert by_key[("broadcast", 1)]["probe_messages"] > 0
    for p in SERVING_POLICIES:
        assert by_key[(p, 4)]["hit_rate"] == by_key[(p, 1)]["hit_rate"]
        assert by_key[(p, 4)]["probe_messages"] \
            == by_key[(p, 1)]["probe_messages"]
        assert by_key[(p, 4)]["requests_per_kcycle"] \
            > by_key[(p, 1)]["requests_per_kcycle"]
