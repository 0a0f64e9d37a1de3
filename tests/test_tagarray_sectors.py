"""Sectored tag-array updates against a plain sequential model: fills
apply in slot (or request) order, a way holding the line at that moment
takes the OR of the sectors and the dirty bit, any other is replaced;
touches OR their sectors in. Row form, row form under ``vmap``, and the
scatter form all agree with it, duplicates and all."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tagarray

SHAPE = (3, 2, 2)                    # arrays, sets, ways
FIELDS = ("tags", "valid", "dirty", "last", "born", "sect")


def _state(rng):
    """A random sectored state whose small tag range makes requests
    find their line often."""
    state = tagarray.init_tag_state(*SHAPE, sectors=4)
    state.update(
        tags=jnp.asarray(rng.integers(0, 4, SHAPE), jnp.int32),
        valid=jnp.asarray(rng.random(SHAPE) < 0.7),
        dirty=jnp.asarray(rng.random(SHAPE) < 0.5),
        last=jnp.asarray(rng.integers(-1, 5, SHAPE), jnp.int32),
        born=jnp.asarray(rng.integers(-1, 5, SHAPE), jnp.int32),
        sect=jnp.asarray(rng.integers(0, 16, SHAPE), jnp.int32))
    return state


def _requests(rng, n):
    """(set, way, addr, mask, dirty, sect) of ``n`` requests, with many
    duplicate targets."""
    return (rng.integers(0, SHAPE[1], n), rng.integers(0, SHAPE[2], n),
            rng.integers(0, 4, n), rng.random(n) < 0.85,
            rng.random(n) < 0.4, rng.integers(1, 16, n))


def _sequential_fill(state, arrays, reqs, now):
    """The model: requests applied one at a time, in order. Returns the
    state and each request's evicted dirty sectors."""
    st = {k: np.array(state[k]) for k in FIELDS}
    s, w, addr, mask, dirty, sect = reqs
    evicted = np.zeros(len(addr), np.int64)
    for i in np.nonzero(mask)[0]:
        e = (arrays[i], s[i], w[i])
        if st["valid"][e] and st["tags"][e] == addr[i]:
            st["sect"][e] |= sect[i]
            st["dirty"][e] |= dirty[i]
        else:
            if st["valid"][e] and st["dirty"][e]:
                evicted[i] = st["sect"][e]
            st["tags"][e], st["valid"][e] = addr[i], True
            st["sect"][e], st["dirty"][e] = sect[i], dirty[i]
            st["born"][e] = now
        st["last"][e] = max(st["last"][e], now)
    return st, evicted


def _assert_state(got, want):
    for k in FIELDS:
        np.testing.assert_array_equal(np.asarray(got[k]), want[k],
                                      err_msg=k)


@pytest.mark.parametrize("k", [2, 4])
@pytest.mark.parametrize("seed", range(6))
def test_fill_rows_or_merges_in_slot_order(k, seed):
    rng = np.random.default_rng(seed)
    state = _state(rng)
    n = SHAPE[0] * k
    reqs = _requests(rng, n)
    arrays = np.repeat(np.arange(SHAPE[0]), k)
    want, want_ev = _sequential_fill(state, arrays, reqs, now=7)
    s, w, addr, mask, dirty, sect = (jnp.asarray(x) for x in reqs)
    got, ev = tagarray.fill_rows(state, s.astype(jnp.int32),
                                 w.astype(jnp.int32),
                                 addr.astype(jnp.int32), jnp.int32(7), mask,
                                 dirty=dirty, sect=sect.astype(jnp.int32))
    _assert_state(got, want)
    np.testing.assert_array_equal(np.asarray(ev), want_ev)


@pytest.mark.parametrize("seed", range(3))
def test_fill_rows_or_merges_under_vmap(seed):
    """Two states filled at once under ``vmap`` equal each filled
    alone."""
    rng = np.random.default_rng(100 + seed)
    k = 4
    states = [_state(rng) for _ in range(2)]
    reqs = [_requests(rng, SHAPE[0] * k) for _ in range(2)]
    stacked = jax.tree.map(lambda *x: jnp.stack(x), *states)
    s, w, addr, mask, dirty, sect = (
        jnp.asarray(np.stack(x)) for x in zip(*reqs))

    def one(st, s, w, addr, mask, dirty, sect):
        return tagarray.fill_rows(st, s, w, addr, jnp.int32(5), mask,
                                  dirty=dirty, sect=sect)

    got, ev = jax.vmap(one)(stacked, s.astype(jnp.int32),
                            w.astype(jnp.int32), addr.astype(jnp.int32),
                            mask, dirty, sect.astype(jnp.int32))
    arrays = np.repeat(np.arange(SHAPE[0]), k)
    for b in range(2):
        want, want_ev = _sequential_fill(states[b], arrays, reqs[b], now=5)
        _assert_state(jax.tree.map(lambda x: x[b], got), want)
        np.testing.assert_array_equal(np.asarray(ev[b]), want_ev)


@pytest.mark.parametrize("seed", range(6))
def test_scatter_fill_or_merges_in_request_order(seed):
    """The scatter form (the L2's), clean fills to any array in request
    order, duplicate targets resolved before the scatter."""
    rng = np.random.default_rng(200 + seed)
    state = _state(rng)
    n = 12
    s, w, addr, mask, _, sect = _requests(rng, n)
    arrays = rng.integers(0, SHAPE[0], n)
    reqs = (s, w, addr, mask, np.zeros(n, bool), sect)
    want, want_ev = _sequential_fill(state, arrays, reqs, now=9)
    got, ev = tagarray.fill(
        state, *(jnp.asarray(x, jnp.int32) for x in (arrays, s, w)),
        jnp.asarray(addr, jnp.int32), jnp.int32(9), jnp.asarray(mask),
        sect=jnp.asarray(sect, jnp.int32))
    _assert_state(got, want)
    np.testing.assert_array_equal(np.asarray(ev), want_ev)


@pytest.mark.parametrize("seed", range(3))
def test_touch_ors_sectors_into_its_ways(seed):
    rng = np.random.default_rng(300 + seed)
    state = _state(rng)
    n = 12
    s, w, _, mask, _, sect = _requests(rng, n)
    arrays = rng.integers(0, SHAPE[0], n)
    want = {k: np.array(state[k]) for k in FIELDS}
    for i in np.nonzero(mask)[0]:
        e = (arrays[i], s[i], w[i])
        want["sect"][e] |= sect[i]
        want["last"][e] = max(want["last"][e], 4)
    got = tagarray.touch(
        state, *(jnp.asarray(x, jnp.int32) for x in (arrays, s, w)),
        jnp.int32(4), jnp.asarray(mask), sect=jnp.asarray(sect, jnp.int32))
    _assert_state(got, want)


def test_whole_line_states_have_no_sector_leaf():
    assert "sect" not in tagarray.init_tag_state(*SHAPE)
    assert tagarray.init_tag_state(*SHAPE, sectors=4)["sect"].shape == SHAPE


def test_sectored_scatter_fill_refuses_dirty_lines():
    state = tagarray.init_tag_state(*SHAPE, sectors=4)
    z = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="clean"):
        tagarray.fill(state, z, z, z, z, jnp.int32(0), jnp.ones(2, bool),
                      dirty=jnp.ones(2, bool), sect=z + 1)
