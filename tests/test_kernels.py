"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.ata_tag_probe import ata_tag_probe
from repro.kernels.flash_attention import flash_attention
from repro.kernels.wkv6 import wkv6

RNG = np.random.default_rng(42)


def randn(*shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


# ---------------------------------------------------------------------------
# ata_tag_probe
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("R,C,S,W,br", [
    (128, 8, 8, 64, 64),
    (256, 16, 8, 64, 128),
    (64, 30, 16, 8, 32),
    (32, 2, 2, 4, 8),
])
def test_ata_tag_probe_sweep(R, C, S, W, br):
    tags = jnp.asarray(RNG.integers(0, 4096, (C, S, W)), jnp.int32)
    valid = jnp.asarray(RNG.random((C, S, W)) < 0.7)
    qtag = jnp.asarray(RNG.integers(0, 4096, R), jnp.int32)
    set_idx = jnp.asarray(RNG.integers(0, S, R), jnp.int32)
    h1, w1 = ata_tag_probe(set_idx, qtag, tags, valid, br=br,
                           interpret=True)
    h2, w2 = ref.ata_tag_probe_ref(set_idx, qtag, tags, valid)
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
    np.testing.assert_array_equal(
        np.where(np.asarray(h1), np.asarray(w1), 0),
        np.where(np.asarray(h2), np.asarray(w2), 0))


def test_ata_tag_probe_planted_hits():
    C, S, W, R = 4, 8, 16, 64
    tags = jnp.zeros((C, S, W), jnp.int32)
    valid = jnp.zeros((C, S, W), bool)
    qtag = jnp.asarray(RNG.integers(1, 1000, R), jnp.int32)
    set_idx = jnp.asarray(RNG.integers(0, S, R), jnp.int32)
    tags = tags.at[2, set_idx[5], 3].set(qtag[5])
    valid = valid.at[2, set_idx[5], 3].set(True)
    hits, ways = ata_tag_probe(set_idx, qtag, tags, valid, br=32,
                               interpret=True)
    assert bool(hits[5, 2]) and int(ways[5, 2]) == 3
    assert int(hits.sum()) >= 1


@pytest.mark.parametrize("via_ops", [True, False],
                         ids=["impl_pallas", "kernel_default"])
def test_compiled_kernels_raise_off_tpu(via_ops):
    """The compiled Pallas kernels never fall back to the interpreter:
    off-TPU, asking for them (explicitly, or by the default) raises."""
    assert jax.default_backend() != "tpu"
    C, S, W, R = 2, 4, 8, 32
    tags = jnp.asarray(RNG.integers(0, 64, (C, S, W)), jnp.int32)
    valid = jnp.asarray(RNG.random((C, S, W)) < 0.7)
    qtag = jnp.asarray(RNG.integers(0, 64, R), jnp.int32)
    set_idx = jnp.asarray(RNG.integers(0, S, R), jnp.int32)
    core = jnp.zeros((R,), jnp.int32)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        if via_ops:
            ops.ata_probe(set_idx, qtag, tags, valid, impl="pallas")
        else:
            ata_tag_probe(set_idx, qtag, tags, valid)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        ops.ata_probe_rank(set_idx, qtag, core, core, core > 0, tags,
                           valid, valid, cluster_size=C,
                           impl="pallas")


# ---------------------------------------------------------------------------
# ata_probe_rank (fused probe + winner pick + port arbitration)
# ---------------------------------------------------------------------------
def _rank_inputs(R, C, S, W, G, seed=0, tag_lo=0, tag_hi=48):
    rng = np.random.default_rng(seed)
    tags = jnp.asarray(rng.integers(tag_lo, tag_hi, (C, S, W)), jnp.int32)
    valid = jnp.asarray(rng.random((C, S, W)) < 0.7)
    dirty = jnp.asarray(np.asarray(valid) & (rng.random((C, S, W)) < 0.2))
    qtag = jnp.asarray(rng.integers(tag_lo, tag_hi, R), jnp.int32)
    set_idx = jnp.asarray(rng.integers(0, S, R), jnp.int32)
    core = jnp.asarray(rng.integers(0, C, R), jnp.int32)
    cbase = (core // G) * G
    deny = jnp.asarray(rng.random(R) < 0.2)
    return set_idx, qtag, core, cbase, deny, tags, valid, dirty


def _assert_rank_equal(got, want):
    lh, rok = want[0], want[2]
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(lh))
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(rok))
    masks = (None, lh, None, rok, rok, rok)
    for a, b, m in zip(got, want, masks):
        if m is None:
            continue
        np.testing.assert_array_equal(
            np.where(np.asarray(m), np.asarray(a), 0),
            np.where(np.asarray(m), np.asarray(b), 0))


@pytest.mark.parametrize("R,C,S,W,G,br,seed", [
    (128, 8, 8, 64, 4, 64, 0),
    (256, 12, 8, 16, 4, 128, 0),
    (64, 4, 16, 8, 2, 64, 0),
    (60, 6, 4, 8, 3, 16, 1),      # R % br != 0: dead-lane padding
    (150, 30, 8, 64, 10, 128, 0),  # paper geometry at m=5, padded tile
])
def test_ata_probe_rank_sweep(R, C, S, W, G, br, seed):
    args = _rank_inputs(R, C, S, W, G, seed=seed)
    want = ref.ata_probe_rank_ref(*args, cluster_size=G)
    got = ops.ata_probe_rank(*args, cluster_size=G, impl="interpret",
                             br=br)
    assert np.asarray(want[0]).any() and np.asarray(want[2]).any()
    _assert_rank_equal(got, want)


def test_ata_probe_rank_planted_arbitration():
    """Three requests hitting the same peer must queue 0,1,2 in request
    order with group size 3; a denied fourth stays out of the group."""
    C, S, W, G = 4, 4, 4, 4
    R = 8
    tags = jnp.zeros((C, S, W), jnp.int32)
    valid = jnp.zeros((C, S, W), bool)
    dirty = jnp.zeros((C, S, W), bool)
    # line 7 lives only in cache 2, set 1, way 3
    tags = tags.at[2, 1, 3].set(7)
    valid = valid.at[2, 1, 3].set(True)
    set_idx = jnp.full((R,), 1, jnp.int32)
    qtag = jnp.where(jnp.arange(R) < 4, 7, 9).astype(jnp.int32)
    core = jnp.asarray([0, 1, 3, 0, 1, 2, 3, 0], jnp.int32)
    cbase = jnp.zeros((R,), jnp.int32)
    deny = jnp.asarray([False, False, False, True,
                        False, False, False, False])
    out = ops.ata_probe_rank(set_idx, qtag, core, cbase, deny, tags,
                             valid, dirty, cluster_size=G,
                             impl="interpret", br=4)
    local, way, rok, src, rank, size = (np.asarray(x) for x in out)
    assert not local.any()
    assert rok.tolist() == [True, True, True, False,
                            False, False, False, False]
    assert src[:3].tolist() == [2, 2, 2]
    assert rank[:3].tolist() == [0, 1, 2]       # request order
    assert size[:3].tolist() == [3, 3, 3]
    assert size[3] == 0                          # denied: no port slot
    ref_out = ref.ata_probe_rank_ref(set_idx, qtag, core, cbase, deny,
                                     tags, valid, dirty, cluster_size=G)
    _assert_rank_equal(out, ref_out)


def test_ata_probe_rank_counts_carry_across_tiles():
    """br=4 over R=16 with every request targeting one peer: ranks must
    continue across tile boundaries (the carried VMEM counter), not
    restart at 0 per tile."""
    C, S, W, G = 2, 2, 2, 2
    R = 16
    tags = jnp.zeros((C, S, W), jnp.int32).at[1, 0, 1].set(5)
    valid = jnp.zeros((C, S, W), bool).at[1, 0, 1].set(True)
    dirty = jnp.zeros((C, S, W), bool)
    set_idx = jnp.zeros((R,), jnp.int32)
    qtag = jnp.full((R,), 5, jnp.int32)
    core = jnp.zeros((R,), jnp.int32)
    cbase = jnp.zeros((R,), jnp.int32)
    deny = jnp.zeros((R,), bool)
    out = ops.ata_probe_rank(set_idx, qtag, core, cbase, deny, tags,
                             valid, dirty, cluster_size=G,
                             impl="interpret", br=4)
    _, _, rok, _, rank, size = (np.asarray(x) for x in out)
    assert rok.all()
    assert rank.tolist() == list(range(R))
    assert (size == R).all()


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,Hq,Hkv,Tq,Tk,D,bq,bk,causal,window", [
    (1, 4, 4, 128, 128, 64, 64, 64, True, None),
    (2, 8, 2, 256, 256, 64, 128, 128, True, None),     # GQA
    (1, 4, 2, 128, 128, 32, 64, 32, True, 48),         # window
    (2, 4, 4, 64, 64, 128, 64, 64, False, None),       # bidirectional
    (1, 2, 1, 1, 128, 64, 1, 64, False, None),         # decode Tq=1
])
def test_flash_attention_sweep(B, Hq, Hkv, Tq, Tk, D, bq, bk, causal,
                               window):
    q = randn(B, Hq, Tq, D, scale=0.5)
    k = randn(B, Hkv, Tk, D, scale=0.5)
    v = randn(B, Hkv, Tk, D, scale=0.5)
    o1 = flash_attention(q, k, v, causal=causal, window=window,
                         bq=bq, bk=bk)
    o2 = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_kv_len():
    B, Hq, Hkv, Tk, D = 2, 4, 2, 128, 64
    q = randn(B, Hq, 1, D)
    k = randn(B, Hkv, Tk, D)
    v = randn(B, Hkv, Tk, D)
    kl = jnp.asarray([37, 100], jnp.int32)
    o1 = flash_attention(q, k, v, kv_len=kl, causal=False, bq=1, bk=32)
    o2 = ref.attention_len_ref(q, k, v, kl, causal=False)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_bf16():
    q = randn(1, 2, 64, 64, dtype=jnp.bfloat16)
    k = randn(1, 2, 64, 64, dtype=jnp.bfloat16)
    v = randn(1, 2, 64, 64, dtype=jnp.bfloat16)
    o1 = flash_attention(q, k, v, causal=True, bq=32, bk=32)
    o2 = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32),
                               rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# wkv6
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,H,T,K,V,chunk", [
    (1, 2, 128, 64, 64, 64),
    (2, 3, 192, 64, 64, 32),
    (1, 1, 64, 32, 64, 64),     # K != V
    (2, 2, 256, 64, 64, 128),
])
def test_wkv6_sweep(B, H, T, K, V, chunk):
    r = randn(B, H, T, K, scale=0.5)
    k = randn(B, H, T, K, scale=0.5)
    v = randn(B, H, T, V, scale=0.5)
    w = -jnp.exp(randn(B, H, T, K))
    u = randn(H, K, scale=0.5)
    o1, s1 = wkv6(r, k, v, w, u, chunk=chunk)
    o2, s2 = ref.wkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=2e-3, atol=2e-4)


def test_wkv6_initial_state_chaining():
    """Processing [first half] then [second half] == whole sequence."""
    B, H, T, K = 1, 2, 128, 64
    r = randn(B, H, T, K, scale=0.5)
    k = randn(B, H, T, K, scale=0.5)
    v = randn(B, H, T, K, scale=0.5)
    w = -jnp.exp(randn(B, H, T, K))
    u = randn(H, K, scale=0.5)
    o_full, s_full = wkv6(r, k, v, w, u, chunk=32)
    h = T // 2
    o1, s1 = wkv6(r[:, :, :h], k[:, :, :h], v[:, :, :h], w[:, :, :h],
                  u, chunk=32)
    o2, s2 = wkv6(r[:, :, h:], k[:, :, h:], v[:, :, h:], w[:, :, h:],
                  u, initial_state=s1, chunk=32)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2], 2)),
                               np.asarray(o_full), rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               rtol=2e-3, atol=2e-4)


def test_wkv6_strong_decay_stable():
    B, H, T, K = 1, 1, 128, 64
    r = randn(B, H, T, K)
    k = randn(B, H, T, K)
    v = randn(B, H, T, K)
    w = jnp.full((B, H, T, K), -20.0)          # near-total decay
    u = randn(H, K)
    o, s = wkv6(r, k, v, w, u, chunk=64)
    assert not bool(jnp.isnan(o).any())
    assert not bool(jnp.isinf(o).any())
