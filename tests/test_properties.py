"""Hypothesis property tests, collected from across the suite.

They live in their own module so that a missing ``hypothesis`` (the
optional ``test`` extra) degrades to *these* tests skipping while the
example-based tests in test_simulator/test_substrate/test_serving keep
running.
"""
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need the 'test' extra "
    "(pip install -e .[test])")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import tagarray
from repro.core.arch.ata import AtaPolicy
from repro.core.arch.ciao import CiaoPolicy
from repro.core.arch.private import PrivatePolicy
from repro.core.arch.victim import VictimPolicy
from repro.core.contention import (_group_rank_onehot, group_prefix_sum,
                                   group_rank)
from repro.core.geometry import GpuGeometry
from repro.core.simulator import _request_batch
from repro.optim.compression import compress, decompress
from repro.serving import hash_blocks


# ---------------------------------------------------------------------------
# group_rank: the one contention primitive
# ---------------------------------------------------------------------------
@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 7), min_size=1, max_size=40),
       st.data())
def test_group_rank_matches_python(keys, data):
    mask = data.draw(st.lists(st.booleans(), min_size=len(keys),
                              max_size=len(keys)))
    k = jnp.asarray(keys, jnp.int32)
    m = jnp.asarray(mask)
    rank, size = group_rank(k, m, 8)
    seen = {}
    for i, (key, on) in enumerate(zip(keys, mask)):
        if not on:
            assert int(rank[i]) == 0 and int(size[i]) == 0
            continue
        assert int(rank[i]) == seen.get(key, 0)
        seen[key] = seen.get(key, 0) + 1
    for i, (key, on) in enumerate(zip(keys, mask)):
        if on:
            assert int(size[i]) == seen[key]


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 64), st.integers(1, 200), st.data())
def test_group_rank_sorted_path_matches_onehot_reference(n_keys, R, data):
    """The hot sort/segment-sum path must return the *identical*
    integers as the O(R*K) one-hot reference — downstream float timing
    (and thus every golden) is bit-exact iff the ranks are."""
    rng = np.random.default_rng(data.draw(st.integers(0, 2**32 - 1)))
    keys = jnp.asarray(rng.integers(0, n_keys, R), jnp.int32)
    mask = jnp.asarray(rng.random(R) < data.draw(st.floats(0.0, 1.0)))
    rank_s, size_s = group_rank(keys, mask, n_keys)
    rank_r, size_r = _group_rank_onehot(keys, mask, n_keys)
    assert (np.asarray(rank_s) == np.asarray(rank_r)).all()
    assert (np.asarray(size_s) == np.asarray(size_r)).all()


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 16), st.integers(1, 80), st.data())
def test_group_prefix_sum_matches_python(n_keys, R, data):
    """The weighted generalization (NoC port arbitration) against a
    sequential python accumulator."""
    rng = np.random.default_rng(data.draw(st.integers(0, 2**32 - 1)))
    keys = rng.integers(0, n_keys, R)
    vals = rng.integers(0, 9, R).astype(np.float32)
    mask = rng.random(R) < 0.7
    before, total = group_prefix_sum(
        jnp.asarray(keys, jnp.int32), jnp.asarray(vals),
        jnp.asarray(mask), n_keys)
    acc = {}
    for i in range(R):
        if mask[i]:
            assert float(before[i]) == acc.get(keys[i], 0.0), i
            acc[keys[i]] = acc.get(keys[i], 0.0) + float(vals[i])
        else:
            assert float(before[i]) == 0.0 and float(total[i]) == 0.0
    for i in range(R):
        if mask[i]:
            assert float(total[i]) == acc[keys[i]]


# ---------------------------------------------------------------------------
# LRU tag array vs a pure-python reference cache
# ---------------------------------------------------------------------------
@settings(max_examples=15, deadline=None)
@given(st.lists(st.integers(0, 30), min_size=5, max_size=60))
def test_tagarray_lru_matches_reference(addrs):
    n_sets, n_ways = 2, 3
    state = tagarray.init_tag_state(1, n_sets, n_ways)
    ref = {s: [] for s in range(n_sets)}     # list of addrs, MRU last
    for t, a in enumerate(addrs):
        s = a % n_sets
        arr = jnp.asarray([a], jnp.int32)
        si = jnp.asarray([s], jnp.int32)
        zero = jnp.asarray([0], jnp.int32)
        hit, way, _ = tagarray.probe(state, zero, si, arr)
        ref_hit = a in ref[s]
        assert bool(hit[0]) == ref_hit, (t, a)
        if ref_hit:
            state = tagarray.touch(state, zero, si, way,
                                   jnp.int32(t), jnp.asarray([True]))
            ref[s].remove(a)
            ref[s].append(a)
        else:
            state, _ = tagarray.fill(state, zero, si, way, arr,
                                     jnp.int32(t), jnp.asarray([True]))
            if len(ref[s]) == n_ways:
                ref[s].pop(0)                 # evict LRU
            ref[s].append(a)


# ---------------------------------------------------------------------------
# scatter-mask invariants: touch/fill mutate masked-in targets only
# ---------------------------------------------------------------------------
@settings(max_examples=25, deadline=None)
@given(st.data())
def test_fill_and_touch_scatter_mask_invariants(data):
    n_arrays, n_sets, n_ways = 3, 2, 2
    R = data.draw(st.integers(1, 12))
    idx = st.lists(st.integers(0, 10**6), min_size=R, max_size=R)
    a = np.asarray(data.draw(idx)) % n_arrays
    s = np.asarray(data.draw(idx)) % n_sets
    w = np.asarray(data.draw(idx)) % n_ways
    addr = np.asarray(data.draw(idx), np.int32) + 1
    mask = np.asarray(data.draw(
        st.lists(st.booleans(), min_size=R, max_size=R)))
    dirty = np.asarray(data.draw(
        st.lists(st.booleans(), min_size=R, max_size=R)))

    # a warmed-up state so changes are detectable against non-zeros
    state = tagarray.init_tag_state(n_arrays, n_sets, n_ways)
    warm_a = np.arange(n_arrays).repeat(n_sets * n_ways) % n_arrays
    warm_s = (np.arange(n_arrays * n_sets * n_ways) // n_ways) % n_sets
    warm_w = np.arange(n_arrays * n_sets * n_ways) % n_ways
    state, _ = tagarray.fill(
        state, jnp.asarray(warm_a, jnp.int32), jnp.asarray(warm_s, jnp.int32),
        jnp.asarray(warm_w, jnp.int32),
        jnp.asarray(1000 + np.arange(warm_a.size), jnp.int32),
        jnp.int32(1), jnp.asarray(np.ones(warm_a.size, bool)))

    filled, _ = tagarray.fill(
        state, jnp.asarray(a, jnp.int32), jnp.asarray(s, jnp.int32),
        jnp.asarray(w, jnp.int32), jnp.asarray(addr), jnp.int32(5),
        jnp.asarray(mask), dirty=jnp.asarray(dirty))
    touched = tagarray.touch(
        state, jnp.asarray(a, jnp.int32), jnp.asarray(s, jnp.int32),
        jnp.asarray(w, jnp.int32), jnp.int32(5), jnp.asarray(mask),
        set_dirty=jnp.asarray(dirty))

    targets = {(int(ai), int(si), int(wi))
               for ai, si, wi, m in zip(a, s, w, mask) if m}
    for out in (filled, touched):
        for key in out:
            before, after = np.asarray(state[key]), np.asarray(out[key])
            changed = np.argwhere(before != after)
            for ai, si, wi in changed:
                # every mutation lands on a masked-in target — never on
                # (0,0,0) or anywhere else by accident
                assert (int(ai), int(si), int(wi)) in targets, (
                    key, (ai, si, wi), targets)
    # masked-in fills actually install one of their writers' lines
    tags = np.asarray(filled["tags"])
    for t in targets:
        writers = [int(x) for x, (ai, si, wi, m) in
                   zip(addr, zip(a, s, w, mask)) if m
                   and (int(ai), int(si), int(wi)) == t]
        assert tags[t] in writers
        assert bool(np.asarray(filled["valid"])[t])
    if not mask.any():
        for key in state:
            np.testing.assert_array_equal(np.asarray(filled[key]),
                                          np.asarray(state[key]))


# ---------------------------------------------------------------------------
# row form: touch_rows/fill_rows equal touch/fill on each row's own array
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("k", [1, 2, 4])
@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_row_form_equals_scatter_form(k, data):
    n_arrays, n_sets, n_ways = 3, 2, 2
    shape = (n_arrays, n_sets, n_ways)

    def draw(elems, dims):
        n = int(np.prod(dims))
        return np.asarray(data.draw(
            st.lists(elems, min_size=n, max_size=n))).reshape(dims)

    state = tagarray.init_tag_state(*shape)
    state.update(
        tags=jnp.asarray(draw(st.integers(0, 9), shape), jnp.int32),
        last=jnp.asarray(draw(st.integers(-1, 9), shape), jnp.int32),
        born=jnp.asarray(draw(st.integers(-1, 9), shape), jnp.int32),
        valid=jnp.asarray(draw(st.booleans(), shape)),
        dirty=jnp.asarray(draw(st.booleans(), shape)))
    rows = (n_arrays, k)
    s = draw(st.integers(0, n_sets - 1), rows)
    w = draw(st.integers(0, n_ways - 1), rows)
    addr = draw(st.integers(10, 99), rows)
    mask = draw(st.booleans(), rows)
    flags = draw(st.booleans(), rows)            # set_dirty / fill dirty
    now = data.draw(st.integers(0, 9))
    # a duplicate (set, way) target within row 0, both writers masked
    # in, and a masked-out lane in row 1
    s[0, -1], w[0, -1] = s[0, 0], w[0, 0]
    mask[0, 0] = mask[0, -1] = True
    mask[1, 0] = False

    a = jnp.asarray(np.arange(n_arrays).repeat(k), jnp.int32)
    fs, fw, faddr, fmask, fflags = (jnp.asarray(x.ravel())
                                    for x in (s, w, addr, mask, flags))
    rs, rw, raddr, rmask, rflags = (jnp.asarray(x)
                                    for x in (s, w, addr, mask, flags))
    if not data.draw(st.booleans()):             # the flags left out
        fflags = rflags = None
    t = jnp.int32(now)

    want = tagarray.touch(state, a, fs, fw, t, fmask, set_dirty=fflags)
    got = tagarray.touch_rows(state, rs, rw, t, rmask, set_dirty=rflags)
    want_f, want_ev = tagarray.fill(state, a, fs, fw, faddr, t, fmask,
                                    dirty=fflags)
    got_f, got_ev = tagarray.fill_rows(state, rs, rw, raddr, t, rmask,
                                       dirty=rflags)
    for name, w_state, g_state in (("touch", want, got),
                                   ("fill", want_f, got_f)):
        assert set(w_state) == set(g_state)
        for key in w_state:
            np.testing.assert_array_equal(np.asarray(g_state[key]),
                                          np.asarray(w_state[key]),
                                          err_msg=f"{name}: {key}")
    assert got_ev.shape == rows
    np.testing.assert_array_equal(np.asarray(got_ev).ravel(),
                                  np.asarray(want_ev))


# ---------------------------------------------------------------------------
# policy-zoo degeneracy: zero-sized extensions change nothing, bit-exactly
# ---------------------------------------------------------------------------
#: Small geometry so random traces exercise hits, misses and evictions.
_ZOO_GEOM = GpuGeometry(n_cores=4, cluster_size=2, l1_sets=2, l1_ways=2,
                        l1_banks=2, l2_parts=2, l2_sets=4, l2_ways=2)
_ZOO_M = 2


def _zoo_state_and_reqs(data, *, victim_ways=0, thrash_lanes=0):
    """A randomly warmed L1 state plus one random round's requests."""
    g = _ZOO_GEOM
    state = tagarray.init_tag_state(g.n_cores, g.l1_sets, g.l1_ways,
                                    victim_ways=victim_ways,
                                    thrash_lanes=thrash_lanes)
    R = g.n_cores * _ZOO_M
    lines = st.lists(st.integers(0, 15), min_size=R, max_size=R)
    core = jnp.asarray(np.arange(g.n_cores).repeat(_ZOO_M), jnp.int32)
    for t in range(data.draw(st.integers(1, 3))):    # warm-up fills
        addr = jnp.asarray(data.draw(lines), jnp.int32)
        set_idx = (addr % g.l1_sets).astype(jnp.int32)
        _, way, _ = tagarray.probe(state, core, set_idx, addr)
        state, _ = tagarray.fill(state, core, set_idx, way, addr,
                                 jnp.int32(t), jnp.ones((R,), bool))
    addr = np.asarray(data.draw(lines),
                      np.int32).reshape(g.n_cores, _ZOO_M)
    is_write = np.asarray(data.draw(
        st.lists(st.booleans(), min_size=R, max_size=R))).reshape(
            g.n_cores, _ZOO_M)
    reqs = _request_batch(g, jnp.asarray(addr), jnp.asarray(is_write))
    return state, reqs


def _assert_outcomes_bit_equal(a, b):
    assert set(a.l1.keys()) == set(b.l1.keys())
    for k in a.l1:
        np.testing.assert_array_equal(np.asarray(a.l1[k]),
                                      np.asarray(b.l1[k]), err_msg=k)
    assert (a.bypass_fill is None) == (b.bypass_fill is None)
    for f in a._fields:
        if f in ("l1", "bypass_fill"):
            continue
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f)


@settings(max_examples=15, deadline=None)
@given(st.data())
def test_victim_zero_ways_never_changes_ata_behavior(data):
    """A size-0 victim buffer is an exact no-op: the victim policy's
    round is bit-identical to base ATA on any state and request mix."""
    state, reqs = _zoo_state_and_reqs(data, victim_ways=0)
    t = jnp.int32(7)
    base = AtaPolicy().l1_stage(_ZOO_GEOM, state, reqs, t)
    vic = VictimPolicy(victim_ways=0).l1_stage(_ZOO_GEOM, state, reqs, t)
    _assert_outcomes_bit_equal(vic, base)


@settings(max_examples=10, deadline=None)
@given(st.data())
def test_probe_backend_never_changes_the_round(data):
    """The probe backend is a lowering choice, not a model choice: on
    any warmed state and request mix, every CPU-runnable backend's
    ``l1_stage`` is bit-identical — outputs *and* carried tag state —
    so IPC (a pure function of the rounds) cannot depend on it."""
    state, reqs = _zoo_state_and_reqs(data)
    t = jnp.int32(7)
    base = AtaPolicy().l1_stage(_ZOO_GEOM, state, reqs, t,
                                backend="lax")
    for backend in ("lax_unfused", "pallas_interpret"):
        got = AtaPolicy().l1_stage(_ZOO_GEOM, state, reqs, t,
                                   backend=backend)
        _assert_outcomes_bit_equal(got, base)


@settings(max_examples=15, deadline=None)
@given(st.data())
def test_ciao_zero_threshold_degenerates_to_private(data):
    """thrash_threshold=0 disables CIAO entirely: outcome and carried
    state (thrash counters included) match the private baseline."""
    state, reqs = _zoo_state_and_reqs(data,
                                      thrash_lanes=_ZOO_GEOM.n_cores)
    t = jnp.int32(7)
    base = PrivatePolicy().l1_stage(_ZOO_GEOM, state, reqs, t)
    ciao = CiaoPolicy(thrash_threshold=0).l1_stage(_ZOO_GEOM, state,
                                                   reqs, t)
    _assert_outcomes_bit_equal(ciao, base)


# ---------------------------------------------------------------------------
# per-app attribution: invariant under app relabeling
# ---------------------------------------------------------------------------
#: Small machine so full simulate() stays cheap inside hypothesis.
_MIX_GEOM = GpuGeometry(n_cores=6, cluster_size=3, l1_sets=2, l1_ways=2,
                        l1_banks=2, l2_parts=2, l2_sets=4, l2_ways=2)


def _tiny_trace(data, core_app):
    from repro.core.simulator import Trace
    T, C, m = 12, _MIX_GEOM.n_cores, 2
    n = T * C * m
    addr = np.asarray(
        data.draw(st.lists(st.integers(0, 63), min_size=n, max_size=n)),
        np.int32).reshape(T, C, m)
    is_write = np.asarray(
        data.draw(st.lists(st.booleans(), min_size=n, max_size=n))
    ).reshape(T, C, m)
    return Trace(addr=addr, is_write=is_write, insn_per_req=5.0,
                 core_app=core_app)


@settings(max_examples=10, deadline=None)
@given(st.permutations(range(3)), st.data())
def test_per_app_attribution_invariant_under_relabeling(perm, data):
    """Relabeling which app id each core carries must only relabel the
    per-app attribution block — every AppStats follows its app to the
    new slot with identical counters (cores, requests, hits, cycles,
    latency sums), and the whole-trace SimResult is untouched."""
    from repro.core import simulate
    base_ids = np.asarray([0, 0, 1, 1, 2, 2], np.int32)
    perm = np.asarray(perm, np.int32)
    tr = _tiny_trace(data, base_ids)
    relabeled = tr._replace(core_app=perm[base_ids])
    r0 = simulate("ata", tr, _MIX_GEOM)
    r1 = simulate("ata", relabeled, _MIX_GEOM)
    # the simulation itself must not depend on labels at all
    # (identical-NaN l1_latency counts as equal)
    assert all(x == y or (x != x and y != y)
               for x, y in zip(tuple(r0)[:-1], tuple(r1)[:-1]))
    for a in range(3):
        orig, moved = r0.per_app[a], r1.per_app[int(perm[a])]
        assert moved.cores == orig.cores
        assert moved.requests == orig.requests
        assert moved.cycles == orig.cycles
        assert moved.local_hits == orig.local_hits
        assert moved.remote_hits == orig.remote_hits
        assert moved.l1_lat_n == orig.l1_lat_n
        assert moved.l1_lat_sum == pytest.approx(orig.l1_lat_sum,
                                                 rel=1e-6)
        assert moved.instructions == pytest.approx(orig.instructions,
                                                   rel=1e-12)


# ---------------------------------------------------------------------------
# gradient compression (error feedback)
# ---------------------------------------------------------------------------
@settings(max_examples=20, deadline=None)
@given(st.lists(st.floats(-10, 10, allow_nan=False), min_size=4,
                max_size=64))
def test_compress_error_feedback_bounded(vals):
    g = jnp.asarray(vals, jnp.float32)
    err = jnp.zeros_like(g)
    q, scale, new_err = compress(g, err)
    rec = decompress(q, scale)
    # EF invariant: rec + new_err == g (+ old err) exactly
    np.testing.assert_allclose(np.asarray(rec + new_err), np.asarray(g),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(new_err).max()) <= float(scale) / 2 + 1e-6


# ---------------------------------------------------------------------------
# serving prefix hash
# ---------------------------------------------------------------------------
@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 999), min_size=32, max_size=96),
       st.integers(1, 31))
def test_hash_blocks_prefix_property(tokens, cut):
    """Equal prefixes hash equally; diverging blocks diverge after."""
    toks = np.asarray(tokens)
    block = 16
    h1 = hash_blocks(toks, block)
    mod = toks.copy()
    mod[min(cut, len(mod) - 1)] += 1
    h2 = hash_blocks(mod, block)
    cut_block = min(cut, len(mod) - 1) // block
    np.testing.assert_array_equal(h1[:cut_block], h2[:cut_block])
    if len(h1) > cut_block:
        assert (h1[cut_block:] != h2[cut_block:]).all()


# ---------------------------------------------------------------------------
# serving engine: batched admission is slot-sequential by contract
# ---------------------------------------------------------------------------
#: One fixed config so every example reuses the per-(policy, B)
#: executables instead of recompiling (small directory for evictions).
_SERVE_CFG = None


def _serve_cfg():
    global _SERVE_CFG
    if _SERVE_CFG is None:
        from repro.serving.engine import ServingConfig
        _SERVE_CFG = ServingConfig(n_sets=8, n_ways=2)
    return _SERVE_CFG


@settings(max_examples=8, deadline=None)
@given(st.sampled_from(("ata", "private", "broadcast")),
       st.sampled_from((2, 4)),
       st.lists(st.sampled_from(("chat", "rag", "batch")), min_size=1,
                max_size=3, unique=True),
       st.integers(0, 1000))
def test_batched_serve_equals_slot_sequential(policy, B, tenants, seed):
    """The batched round contract, as a property: serving a stream at
    ``B`` slots per shard per round IS serving its slot-sequentialized
    ``B=1`` relabeling — every counter integer-for-integer, every
    per-request array bit-equal — across policies, slot counts, mixes
    and seeds. Only the admission-round critical-path aggregation
    (``cycles``, hence modeled throughput) may differ."""
    from repro.core.trace.serving import ServingMix
    from repro.serving.engine import serve_stream
    stream = ServingMix(tuple(tenants)).make_stream(
        n_shards=4, rounds=24, seed=seed, slots=B)
    cfg = _serve_cfg()
    rb = serve_stream(policy, stream, cfg)
    r1 = serve_stream(policy, stream.slot_sequential(), cfg)
    assert rb.slots == B and r1.slots == 1
    for f in ("n_requests", "local_hits", "remote_hits",
              "recomputed_blocks", "probe_messages",
              "remote_fetch_blocks", "directory_sync_entries"):
        assert getattr(rb, f) == getattr(r1, f), f
    for f in ("shard_load", "latency", "served", "tenant_requests",
              "tenant_hit_blocks", "tenant_blocks",
              "tenant_latency_sum"):
        np.testing.assert_array_equal(
            np.asarray(getattr(rb, f)), np.asarray(getattr(r1, f)),
            err_msg=f)
    assert rb.noc_injected == r1.noc_injected
    # batching can only shorten the modeled critical path
    assert rb.cycles <= r1.cycles


# ---------------------------------------------------------------------------
# serving request streams: mix superposition
# ---------------------------------------------------------------------------
@settings(max_examples=12, deadline=None)
@given(st.sampled_from(("chat", "rag", "batch")),
       st.integers(2, 6), st.integers(8, 48), st.integers(0, 1000))
def test_one_tenant_mix_equals_solo_stream(tenant, n_shards, rounds,
                                           seed):
    """A ``ServingMix`` of one tenant IS that tenant's solo stream —
    superposition adds nothing when there is nothing to superpose
    (slot 0 applies no hash-space offset, no contention to arbitrate),
    so the engine replays both identically by construction."""
    from repro.core.trace.serving import ServingMix, tenant_stream
    solo = tenant_stream(tenant, n_shards=n_shards, rounds=rounds,
                         seed=seed, slot=0)
    mix = ServingMix((tenant,)).make_stream(n_shards=n_shards,
                                            rounds=rounds, seed=seed)
    assert mix.tenants == (tenant,)
    np.testing.assert_array_equal(mix.valid, solo.valid)
    np.testing.assert_array_equal(mix.hashes, solo.hashes)
    np.testing.assert_array_equal(mix.n_blocks, solo.n_blocks)
    np.testing.assert_array_equal(mix.tenant, solo.tenant)
