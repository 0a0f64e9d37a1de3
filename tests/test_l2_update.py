"""The packed L2 state's one-scatter update against a plain sequential
model of the per-field scatter forms it replaces: every touch, then
every fill, each in request order. Touches refresh ``last`` (and, with
sectors, OR their fetched sectors in); whole-line fills install their
line; sectored fills OR into a way holding their line at that moment
and replace any other. Targets are drawn from a few entries, so
duplicates, and touches and fills of one entry in one round, are the
rule; masked-out lanes change nothing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tagarray

SHAPE = (3, 2, 4)                    # partitions, sets, ways
FIELDS = ("tags", "last", "valid", "sect")
ROWS = dict(zip(FIELDS, (tagarray.L2_TAG, tagarray.L2_LAST,
                         tagarray.L2_VALID, tagarray.L2_SECT)))


def _fields(rng, sectors):
    """A random state as per-field arrays; its small tag range makes
    fills find their line often."""
    out = {"tags": rng.integers(0, 4, SHAPE),
           "last": rng.integers(-1, 5, SHAPE),
           "valid": rng.random(SHAPE) < 0.7}
    if sectors > 1:
        out["sect"] = rng.integers(0, 16, SHAPE)
    return out


def _packed(fields):
    """The packed ``(P, S, F * W)`` state of per-field arrays."""
    rec = np.stack([np.asarray(fields[k], np.int32)
                    for k in FIELDS if k in fields], axis=2)
    return jnp.asarray(rec.reshape(*SHAPE[:2], -1))


def _unpacked(state):
    """``(P, S, F, W)``: field ``f`` of the packed state at ``[:, :, f]``."""
    return np.asarray(state).reshape(*SHAPE[:2], -1, SHAPE[2])


def _requests(rng, n):
    """(part, set, way, addr, touch, fill, fetch, need) of ``n``
    requests whose targets come from at most 4 entries; each request
    touches, fills or is masked out."""
    entries = np.stack([rng.integers(0, d, 4) for d in SHAPE], axis=1)
    p, s, w = entries[rng.integers(0, 4, n)].T
    kind = rng.integers(0, 3, n)
    return (p, s, w, rng.integers(0, 4, n), kind == 0, kind == 1,
            rng.integers(0, 16, n), rng.integers(1, 16, n))


def _sequential(fields, reqs, now):
    """The model: touches, then fills, one request at a time."""
    st = {k: np.array(v) for k, v in fields.items()}
    sectored = "sect" in st
    p, s, w, addr, touch, fill, fetch, need = reqs
    for i in np.nonzero(touch)[0]:
        e = (p[i], s[i], w[i])
        st["last"][e] = max(st["last"][e], now)
        if sectored:
            st["sect"][e] |= fetch[i]
    for i in np.nonzero(fill)[0]:
        e = (p[i], s[i], w[i])
        if sectored:
            if st["valid"][e] and st["tags"][e] == addr[i]:
                st["sect"][e] |= need[i]
            else:
                st["sect"][e] = need[i]
        st["tags"][e], st["valid"][e] = addr[i], True
        st["last"][e] = max(st["last"][e], now)
    return st


def _update(state, reqs, now):
    """``update_l2`` of ``reqs`` on a packed state (jit-traceable)."""
    p, s, w, addr, touch, fill, fetch, need = reqs
    sect = ({"fetch": fetch, "need": need}
            if state.shape[2] > tagarray.L2_SECT * SHAPE[2] else {})
    return tagarray.update_l2(state, p, s, w, state[p, s], addr, now, touch,
                              fill, **sect)


def _as_jnp(reqs):
    return tuple(jnp.asarray(x) if np.asarray(x).dtype == bool
                 else jnp.asarray(x, jnp.int32) for x in reqs)


def _assert_packed(got, want):
    got = _unpacked(got)
    for k, v in want.items():
        np.testing.assert_array_equal(got[:, :, ROWS[k]],
                                      np.asarray(v, np.int32), err_msg=k)


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("sectors", [1, 4])
def test_update_equals_touches_then_fills_in_request_order(sectors, seed):
    rng = np.random.default_rng(seed)
    fields = _fields(rng, sectors)
    reqs = _requests(rng, 16)
    got = _update(_packed(fields), _as_jnp(reqs), jnp.int32(6))
    _assert_packed(got, _sequential(fields, reqs, now=6))


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("sectors", [1, 4])
def test_update_equals_the_per_field_scatter_forms(sectors, seed):
    """The same round through ``touch`` then ``fill`` on a per-field
    state (in order on this backend) ends in the same fields."""
    rng = np.random.default_rng(50 + seed)
    fields = _fields(rng, sectors)
    reqs = _requests(rng, 16)
    p, s, w, addr, touch, fill, fetch, need = _as_jnp(reqs)
    state = tagarray.init_tag_state(*SHAPE, sectors=sectors)
    state.update({k: jnp.asarray(v, state[k].dtype)
                  for k, v in fields.items()})
    kw = (lambda x: {"sect": x}) if sectors > 1 else (lambda x: {})
    state = tagarray.touch(state, p, s, w, jnp.int32(6), touch, **kw(fetch))
    state, _ = tagarray.fill(state, p, s, w, addr, jnp.int32(6), fill,
                             **kw(need))
    got = _update(_packed(fields), (p, s, w, addr, touch, fill, fetch, need),
                  jnp.int32(6))
    _assert_packed(got, {k: state[k] for k in fields})


@pytest.mark.parametrize("sectors", [1, 4])
def test_update_under_vmap_equals_each_point_alone(sectors):
    rng = np.random.default_rng(100 + sectors)
    fields = [_fields(rng, sectors) for _ in range(2)]
    reqs = [_requests(rng, 16) for _ in range(2)]
    stacked = jnp.stack([_packed(f) for f in fields])
    batch = tuple(jnp.stack(x) for x in zip(*map(_as_jnp, reqs)))
    got = jax.vmap(lambda st, r: _update(st, r, jnp.int32(3)))(stacked,
                                                               batch)
    for b in range(2):
        np.testing.assert_array_equal(
            np.asarray(got[b]),
            np.asarray(_update(_packed(fields[b]), _as_jnp(reqs[b]),
                               jnp.int32(3))))
        _assert_packed(got[b], _sequential(fields[b], reqs[b], now=3))


def test_update_with_every_lane_masked_out_changes_nothing():
    rng = np.random.default_rng(7)
    fields = _fields(rng, 4)
    p, s, w, addr, _, _, fetch, need = _requests(rng, 8)
    off = np.zeros(8, bool)
    state = _packed(fields)
    got = _update(state, _as_jnp((p, s, w, addr, off, off, fetch, need)),
                  jnp.int32(9))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(state))


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("sectors", [1, 4])
def test_probe_l2_equals_the_per_field_probe(sectors, seed):
    """Hit and way as ``probe`` finds them (LRU), and the record of
    that way."""
    rng = np.random.default_rng(200 + seed)
    fields = _fields(rng, sectors)
    p, s, _, addr, *_ = _as_jnp(_requests(rng, 16))
    state = tagarray.init_tag_state(*SHAPE, sectors=sectors)
    state.update({k: jnp.asarray(v, state[k].dtype)
                  for k, v in fields.items()})
    want_hit, want_way, _ = tagarray.probe(state, p, s, addr)
    hit, way, record, _ = tagarray.probe_l2(_packed(fields), p, s, addr, SHAPE[2])
    np.testing.assert_array_equal(np.asarray(hit), np.asarray(want_hit))
    np.testing.assert_array_equal(np.asarray(way), np.asarray(want_way))
    for k in fields:
        np.testing.assert_array_equal(
            np.asarray(record[:, ROWS[k]]),
            np.asarray(state[k][p, s, want_way], np.int32), err_msg=k)


def test_empty_l2_state_is_invalid_and_untouched():
    state = tagarray.init_l2_state(*SHAPE, sectors=4)
    assert state.shape == (SHAPE[0], SHAPE[1], SHAPE[2] * 4)
    state = _unpacked(state)
    assert (state[:, :, tagarray.L2_VALID] == 0).all()
    assert (state[:, :, tagarray.L2_LAST] == -1).all()
    assert tagarray.init_l2_state(*SHAPE).shape[2] == SHAPE[2] * 3
