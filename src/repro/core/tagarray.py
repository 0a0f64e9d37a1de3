"""Functional (JAX) set-associative tag arrays with pluggable replacement.

State is a dict of arrays so it threads through ``lax.scan`` carries:

    tags : (n_arrays, n_sets, n_ways) int32   line address stored per way
    last : (n_arrays, n_sets, n_ways) int32   last-touch timestamp (LRU)
    born : (n_arrays, n_sets, n_ways) int32   install timestamp (FIFO)
    valid: (n_arrays, n_sets, n_ways) bool
    dirty: (n_arrays, n_sets, n_ways) bool

plus two policy-zoo *state extensions*, zero-sized unless requested at
``init_tag_state`` time (the keys are always present, so every TagState
shares one pytree structure and stacked sweep executables line up):

    vtags : (n_arrays, victim_ways) int32   victim tag buffer per array
    vvalid: (n_arrays, victim_ways) bool    (fully associative, FIFO)
    vborn : (n_arrays, victim_ways) int32   install timestamp per entry
    thrash: (thrash_lanes,) int32           per-lane thrash counters

and, for sectored lines (``sectors > 1``; absent otherwise, since even
a zero-sized leaf changes the compiled programs of whole-line caches):

    sect  : (n_arrays, n_sets, n_ways) int32   valid-sector bit mask

Zero-sized extensions are exact no-ops: ``victim_probe`` returns all
misses and ``victim_insert``/``victim_invalidate`` return the state
unchanged, so architectures that ignore the extensions are bit-exact
with and without them (a hypothesis test asserts this).

Victim selection is controlled by :class:`ReplacementPolicy` (LRU, FIFO,
or deterministic pseudo-random), threaded through ``probe``/``fill`` so
architecture policies in ``repro.core.arch`` can run the same cache
organization under different replacement schemes.

All operations are batched over a request vector. ``probe_many`` is the
pure-jnp form of the paper's *aggregated tag array*: one request compared
against the tag arrays of every cache in its cluster in parallel — the
same computation `repro.kernels.ata_tag_probe` implements as a Pallas TPU
kernel (a test asserts they agree).

Scatter-mask convention: mutating ops (``touch``/``fill``) route
masked-*out* requests to an out-of-bounds array index and scatter with
``mode="drop"``, so they touch no entry at all. (They must *not* be
parked at a valid index like ``(0, 0, 0)`` and scatter their old value
back: XLA resolves duplicate scatter indices last-writer-wins, so a
parked no-op landing after a genuine update to array 0 / set 0 / way 0
would revert it — e.g. a core-0 fill undone, a dirty bit lost, a missed
write-back.) Within the masked-*in* requests, duplicate
(array, set, way) targets still resolve last-writer-wins, matching a
single-ported fill path.

Sectors: a line's tag covers ``sectors`` sector bits. ``sectors_at``
reads the mask of a way ``probe``/``probe_many`` found. With ``sect``
given, ``fill_rows`` and ``fill`` apply their requests in order (slot
order, or request order): a request whose target way holds its line at
that moment ORs its sectors (and dirty bit) into it, any other replaces
the way. ``touch`` ORs ``sect`` into the ways it refreshes. Duplicate
targets of the scatter forms are resolved before the scatter, so every
duplicate writes the same value and the scatter's order cannot matter.

Row form: where every request writes into its *own* array — request
``j`` of row ``a`` into array ``a``, as the simulator's round orders
each core's ``m`` requests — ``touch_rows``/``fill_rows`` apply the
same update with no scatter. Each array field is rewritten by ``k``
in-order masked selects of a one-hot over ``(set, way)``, one per
slot, so duplicate targets within a row resolve last-writer-wins in
slot order by construction, under ``vmap`` too. They equal
``touch``/``fill`` fed ``array_idx = repeat(arange(n_arrays), k)`` on
every field (a hypothesis test asserts this). A TPU applies a
scatter's updates one after another (about 80 ns each on a v5e at
paper geometry); the selects fuse into elementwise passes over the
state.
"""
from __future__ import annotations

import enum
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

TagState = Dict[str, jnp.ndarray]

#: Named scope of the sector compares and merges, nested in the round's
#: stage scopes (``repro.core.simulator``). The benchmark reads it.
SECTOR_SCOPE = "sector"


class ReplacementPolicy(enum.Enum):
    """Victim-selection scheme for ``probe``/``fill``.

    LRU    — least-recently-*touched* way (timestamp ``last``)
    FIFO   — oldest-*installed* way (timestamp ``born``); touches do not
             refresh position
    RANDOM — deterministic hash of the line address over the valid ways
             (invalid ways are still preferred, as in real designs)
    """
    LRU = "lru"
    FIFO = "fifo"
    RANDOM = "random"


def init_tag_state(n_arrays: int, n_sets: int, n_ways: int, *,
                   victim_ways: int = 0, thrash_lanes: int = 0,
                   sectors: int = 1) -> TagState:
    shape = (n_arrays, n_sets, n_ways)
    state = {
        "tags": jnp.zeros(shape, jnp.int32),
        "last": jnp.full(shape, -1, jnp.int32),
        "born": jnp.full(shape, -1, jnp.int32),
        "valid": jnp.zeros(shape, bool),
        "dirty": jnp.zeros(shape, bool),
        # policy-zoo extensions — zero-sized unless a policy asks for
        # them, so the pytree structure is uniform across architectures.
        "vtags": jnp.zeros((n_arrays, victim_ways), jnp.int32),
        "vvalid": jnp.zeros((n_arrays, victim_ways), bool),
        "vborn": jnp.full((n_arrays, victim_ways), -1, jnp.int32),
        "thrash": jnp.zeros((thrash_lanes,), jnp.int32),
    }
    if sectors > 1:
        state["sect"] = jnp.zeros(shape, jnp.int32)
    return state


def sectors_at(state: TagState, array_idx, set_idx, way) -> jnp.ndarray:
    """Valid-sector mask of the ways ``probe`` or ``probe_many`` found
    (indices broadcast); meaningful where the probe hit."""
    return state["sect"][array_idx, set_idx, way]


def _or_reduce(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Bitwise OR of an int32 array along ``axis``."""
    return jax.lax.reduce(x, jnp.int32(0), jax.lax.bitwise_or, (axis,))


def _select_victim(state: TagState, array_idx, set_idx, addr,
                   valid: jnp.ndarray,
                   policy: ReplacementPolicy) -> jnp.ndarray:
    """Victim way per request; invalid ways always win first."""
    int_min = jnp.iinfo(jnp.int32).min
    if policy is ReplacementPolicy.LRU:
        last = state["last"][array_idx, set_idx]
        return jnp.argmin(jnp.where(valid, last, int_min), axis=-1)
    if policy is ReplacementPolicy.FIFO:
        born = state["born"][array_idx, set_idx]
        return jnp.argmin(jnp.where(valid, born, int_min), axis=-1)
    if policy is ReplacementPolicy.RANDOM:
        n_ways = state["tags"].shape[-1]
        # Knuth multiplicative hash of the line address: deterministic,
        # trace-reproducible, uniform over ways.
        h = addr.astype(jnp.uint32) * jnp.uint32(2654435761)
        h = (h >> jnp.uint32(16)) ^ h
        rand_way = (h % jnp.uint32(n_ways)).astype(jnp.int32)
        first_invalid = jnp.argmin(valid, axis=-1).astype(jnp.int32)
        return jnp.where(valid.all(axis=-1), rand_way, first_invalid)
    raise ValueError(f"unknown replacement policy {policy!r}")


def probe(state: TagState, array_idx: jnp.ndarray, set_idx: jnp.ndarray,
          addr: jnp.ndarray,
          policy: ReplacementPolicy = ReplacementPolicy.LRU,
          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Look up one (array, set) per request.

    Returns (hit, way, dirty_hit); way is the hit way or the victim the
    replacement ``policy`` selects.
    """
    tags = state["tags"][array_idx, set_idx]      # (R, W)
    valid = state["valid"][array_idx, set_idx]
    match = (tags == addr[:, None]) & valid
    hit = match.any(axis=-1)
    hit_way = jnp.argmax(match, axis=-1)
    victim = _select_victim(state, array_idx, set_idx, addr, valid, policy)
    way = jnp.where(hit, hit_way, victim)
    dirty_hit = (match & state["dirty"][array_idx, set_idx]).any(axis=-1)
    return hit, way, dirty_hit


def probe_many(state: TagState, arrays: jnp.ndarray, set_idx: jnp.ndarray,
               addr: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Aggregated-tag-array probe: each request vs a *group* of arrays.

    arrays : (R, G) int32 — the G tag arrays (cluster caches) per request
    Returns (hits (R, G), ways (R, G), dirty (R, G)).
    """
    tags = state["tags"][arrays, set_idx[:, None]]    # (R, G, W)
    valid = state["valid"][arrays, set_idx[:, None]]
    match = (tags == addr[:, None, None]) & valid
    hits = match.any(axis=-1)
    ways = jnp.argmax(match, axis=-1)
    dirty = (match & state["dirty"][arrays, set_idx[:, None]]).any(axis=-1)
    return hits, ways, dirty


def _drop_unmasked(state: TagState, array_idx, mask) -> jnp.ndarray:
    """Scatter array index that routes masked-out requests out of bounds.

    Combined with ``mode="drop"`` the scatter then skips them entirely —
    see the scatter-mask convention in the module docstring.
    """
    return jnp.where(mask, array_idx, state["tags"].shape[0])


def touch(state: TagState, array_idx, set_idx, way, now,
          mask, *, set_dirty=None, sect=None) -> TagState:
    """Refresh LRU timestamp (and optionally dirty) for masked requests;
    with ``sect``, OR each request's sectors into its way."""
    a = _drop_unmasked(state, array_idx, mask)
    last = state["last"].at[a, set_idx, way].max(now, mode="drop")
    out = dict(state, last=last)
    if set_dirty is not None:
        ad = _drop_unmasked(state, array_idx, mask & set_dirty)
        out["dirty"] = state["dirty"].at[ad, set_idx, way].set(
            True, mode="drop")
    if sect is not None:
        with jax.named_scope(SECTOR_SCOPE):
            flat = _flat_target(state, array_idx, set_idx, way)
            same = (flat[:, None] == flat[None, :]) & mask[None, :]
            merged = (_or_reduce(jnp.where(same, sect[None, :], 0), 1)
                      | sectors_at(state, array_idx, set_idx, way))
            out["sect"] = state["sect"].at[a, set_idx, way].set(
                merged, mode="drop")
    return out


def fill(state: TagState, array_idx, set_idx, way, addr, now,
         mask, *, dirty=None, sect=None) -> Tuple[TagState, jnp.ndarray]:
    """Install lines for masked requests; returns (state, evicted_dirty).

    Masked-out requests are dropped (see the scatter-mask convention in
    the module docstring); within the masked-in set, duplicate
    (array,set,way) targets resolve last-writer-wins, matching a
    single-ported fill path. ``evicted_dirty`` flags write-back traffic.
    With ``sect``, see :func:`_fill_sectored`.
    """
    if sect is not None:
        if dirty is not None:
            raise ValueError("sectored scatter fills install clean lines")
        return _fill_sectored(state, array_idx, set_idx, way, addr, now,
                              mask, sect)
    a = _drop_unmasked(state, array_idx, mask)
    # Reads use the caller's (always in-bounds) indices; the results are
    # masked, so masked-out lanes never contribute.
    old_valid = state["valid"][array_idx, set_idx, way]
    old_dirty = state["dirty"][array_idx, set_idx, way]
    evicted_dirty = mask & old_valid & old_dirty

    tags = state["tags"].at[a, set_idx, way].set(addr, mode="drop")
    valid = state["valid"].at[a, set_idx, way].set(True, mode="drop")
    last = state["last"].at[a, set_idx, way].max(now, mode="drop")
    born = state["born"].at[a, set_idx, way].set(now, mode="drop")
    new_dirty = dirty if dirty is not None else jnp.zeros_like(mask)
    dirty_arr = state["dirty"].at[a, set_idx, way].set(new_dirty,
                                                       mode="drop")
    # dict(state, ...) so zoo state extensions (victim buffer, thrash
    # counters) ride through untouched.
    return dict(state, tags=tags, last=last, born=born, valid=valid,
                dirty=dirty_arr), evicted_dirty


def _flat_target(state: TagState, array_idx, set_idx, way) -> jnp.ndarray:
    """Flat ``(array, set, way)`` entry index of each request."""
    _, n_sets, n_ways = state["tags"].shape
    return (array_idx * n_sets + set_idx) * n_ways + way


def _fill_sectored(state: TagState, array_idx, set_idx, way, addr, now,
                   mask, sect) -> Tuple[TagState, jnp.ndarray]:
    """``fill`` of clean sectored lines, in request order.

    A request whose target way holds its line at that moment ORs its
    sectors in; any other replaces the way. So each target ends holding
    the line of its last request, with the sectors of the trailing run
    of requests for that line (and the old ones, where no request
    replaced the line the way held). Every request computes its
    target's final entry, so duplicate scatter targets write equal
    values. The second result is the valid-sector mask of each dirty
    line a request evicts (0 elsewhere): only the old line can be
    dirty, since the lines filled here are clean, so a request evicts
    one where it is the first to replace the old line.
    """
    # the merge-or-replace decisions and the sector masks are the
    # sector scope; the writes of the other fields are any fill's
    with jax.named_scope(SECTOR_SCOPE):
        R = addr.shape[0]
        idx = jnp.arange(R, dtype=jnp.int32)
        flat = _flat_target(state, array_idx, set_idx, way)
        same = ((flat[:, None] == flat[None, :])
                & mask[:, None] & mask[None, :])
        last_req = jnp.max(jnp.where(same, idx[None, :], -1), axis=1)
        line = addr[jnp.maximum(last_req, 0)]
        boundary = jnp.max(jnp.where(same & (addr[None, :] != line[:, None]),
                                     idx[None, :], -1), axis=1)
        run = same & (idx[None, :] > boundary[:, None])
        old_tag, old_valid, old_dirty, old_born, old_sect = (
            state[k][array_idx, set_idx, way]
            for k in ("tags", "valid", "dirty", "born", "sect"))
        held = old_valid & (old_tag == line) & (boundary < 0)
        new_sect = (_or_reduce(jnp.where(run, sect[None, :], 0), 1)
                    | jnp.where(held, old_sect, 0))
        before = same & (idx[None, :] < idx[:, None])
        keeps_old = old_valid & (old_tag == addr)
        evicted = (mask & ~keeps_old & old_valid & old_dirty
                   & ~jnp.any(before & ~keeps_old[None, :], axis=1))
        evicted_sect = jnp.where(
            evicted,
            old_sect | _or_reduce(jnp.where(before, sect[None, :], 0), 1), 0)
        a = _drop_unmasked(state, array_idx, mask)
        sect_arr = state["sect"].at[a, set_idx, way].set(new_sect,
                                                         mode="drop")
    at = lambda k: state[k].at[a, set_idx, way]
    out = dict(state,
               tags=at("tags").set(line, mode="drop"),
               valid=at("valid").set(True, mode="drop"),
               last=at("last").max(now, mode="drop"),
               born=at("born").set(jnp.where(held, old_born, now),
                                   mode="drop"),
               dirty=at("dirty").set(held & old_dirty, mode="drop"),
               sect=sect_arr)
    return out, evicted_sect


def _rows(state: TagState, x) -> jnp.ndarray:
    """A request field as ``(n_arrays, k)`` rows (array-major order)."""
    return jnp.reshape(jnp.asarray(x), (state["tags"].shape[0], -1))


def _slot_targets(state: TagState, set_idx, way, mask):
    """Per slot ``j``, the ``(n_arrays, n_sets, n_ways)`` one-hot of each
    row's masked-in ``(set_idx[:, j], way[:, j])`` target.

    Entries are matched by their flat index ``set * n_ways + way`` (one
    compare per slot, which XLA fuses into the selects), so targets must
    be in range, as ``probe``'s sets and ways always are."""
    _, n_sets, n_ways = state["tags"].shape
    entry = jnp.arange(n_sets * n_ways, dtype=jnp.int32).reshape(
        1, n_sets, n_ways)
    target = jnp.where(mask, set_idx * n_ways + way, -1)
    return [entry == target[:, j, None, None]
            for j in range(set_idx.shape[1])]


def touch_rows(state: TagState, set_idx, way, now, mask, *,
               set_dirty=None) -> TagState:
    """``touch`` where slot ``j`` of row ``a`` targets array ``a``.

    Fields are ``(n_arrays, k)``, or their flat array-major
    ``(n_arrays * k,)`` form. See the row form in the module docstring.
    """
    hits = _slot_targets(state, _rows(state, set_idx), _rows(state, way),
                         _rows(state, mask))
    last = state["last"]
    for hit in hits:
        last = jnp.where(hit, jnp.maximum(last, now), last)
    out = dict(state, last=last)
    if set_dirty is not None:
        set_dirty = _rows(state, set_dirty)
        dirty = state["dirty"]
        for j, hit in enumerate(hits):
            dirty = dirty | (hit & set_dirty[:, j, None, None])
        out["dirty"] = dirty
    return out


def fill_rows(state: TagState, set_idx, way, addr, now, mask, *,
              dirty=None, sect=None) -> Tuple[TagState, jnp.ndarray]:
    """``fill`` where slot ``j`` of row ``a`` targets array ``a``.

    Fields are ``(n_arrays, k)``, or their flat array-major
    ``(n_arrays * k,)`` form; ``evicted_dirty`` comes back in ``mask``'s
    shape, read from the state before any slot writes. See the row form
    in the module docstring. With ``sect``, see
    :func:`_fill_rows_sectored`.
    """
    shape = jnp.shape(mask)
    set_idx, way, addr, mask = (_rows(state, x)
                                for x in (set_idx, way, addr, mask))
    new_dirty = (_rows(state, dirty) if dirty is not None
                 else jnp.zeros(addr.shape, bool))
    rows = jnp.arange(addr.shape[0], dtype=jnp.int32)[:, None]
    if sect is not None:
        out, evicted = _fill_rows_sectored(state, rows, set_idx, way, addr,
                                           now, mask, new_dirty,
                                           _rows(state, sect))
        return out, evicted.reshape(shape)
    evicted_dirty = (mask & state["valid"][rows, set_idx, way]
                     & state["dirty"][rows, set_idx, way])
    hits = _slot_targets(state, set_idx, way, mask)
    tags, valid, last, born, dirty_arr = (
        state[k] for k in ("tags", "valid", "last", "born", "dirty"))
    for j, hit in enumerate(hits):
        tags = jnp.where(hit, addr[:, j, None, None], tags)
        valid = valid | hit
        last = jnp.where(hit, jnp.maximum(last, now), last)
        born = jnp.where(hit, now, born)
        dirty_arr = jnp.where(hit, new_dirty[:, j, None, None], dirty_arr)
    return dict(state, tags=tags, last=last, born=born, valid=valid,
                dirty=dirty_arr), evicted_dirty.reshape(shape)


def _fill_rows_sectored(state: TagState, rows, set_idx, way, addr, now,
                        mask, new_dirty, sect):
    """``fill_rows`` of sectored lines, in slot order.

    Slot ``j`` sees its target as the slots before it left it: a way
    holding the slot's line takes the OR of the sectors and dirty bits,
    any other is replaced. What each slot finds there is worked out on
    the ``(n_arrays, k)`` request fields, from the state before any
    slot writes and the earlier slots of its row; the state is then
    rewritten by the row form's in-order selects. The second result is
    the valid-sector mask of each dirty line a slot evicts (0
    elsewhere). The sector scope holds what each slot finds and the
    sector masks' selects; the other fields' selects are any fill's.
    """
    with jax.named_scope(SECTOR_SCOPE):
        k = addr.shape[1]
        n_ways = state["tags"].shape[2]
        flat = set_idx * n_ways + way
        cur = [state[f][rows, set_idx, way]
               for f in ("tags", "valid", "dirty", "sect")]
        after, merged, evicted = [], [], []
        for j in range(k):
            tag, valid, dirt, have = (c[:, j] for c in cur)
            for i in range(j):
                same = mask[:, i] & (flat[:, i] == flat[:, j])
                tag, valid, dirt, have = (
                    jnp.where(same, new, old) for new, old in
                    zip((addr[:, i], True) + after[i], (tag, valid, dirt,
                                                        have)))
            merge = valid & (tag == addr[:, j])
            merged.append(merge)
            after.append((jnp.where(merge, dirt | new_dirty[:, j],
                                    new_dirty[:, j]),
                          jnp.where(merge, have | sect[:, j], sect[:, j])))
            evicted.append(jnp.where(mask[:, j] & ~merge & valid & dirt,
                                     have, 0))
    hits = _slot_targets(state, set_idx, way, mask)
    tags, valid, last, born, dirty_arr, sect_arr = (
        state[f] for f in ("tags", "valid", "last", "born", "dirty", "sect"))
    for j, hit in enumerate(hits):
        tags = jnp.where(hit, addr[:, j, None, None], tags)
        valid = valid | hit
        last = jnp.where(hit, jnp.maximum(last, now), last)
        born = jnp.where(hit & ~merged[j][:, None, None], now, born)
        dirty_arr = jnp.where(hit, after[j][0][:, None, None], dirty_arr)
        with jax.named_scope(SECTOR_SCOPE):
            sect_arr = jnp.where(hit, after[j][1][:, None, None], sect_arr)
    return (dict(state, tags=tags, last=last, born=born, valid=valid,
                 dirty=dirty_arr, sect=sect_arr),
            jnp.stack(evicted, axis=1))


def dead_victim(state: TagState, array_idx: jnp.ndarray,
                set_idx: jnp.ndarray, addr: jnp.ndarray,
                policy: ReplacementPolicy = ReplacementPolicy.LRU,
                ) -> jnp.ndarray:
    """Predict whether a fill for ``addr`` would evict a *dead* line.

    Dead = the replacement victim the ``policy`` would select is valid
    but was never re-touched after its own install (``last == born``) —
    the set is absorbing streaming traffic. Shared detector of the
    CIAO-style policies (``ata_bypass`` fill bypass, ``ciao`` thrash
    counters).
    """
    _, victim, _ = probe(state, array_idx, set_idx, addr, policy=policy)
    last = state["last"][array_idx, set_idx, victim]
    born = state["born"][array_idx, set_idx, victim]
    valid = state["valid"][array_idx, set_idx, victim]
    return valid & (last == born)


# ---------------------------------------------------------------------------
# Victim tag buffer (policy-zoo extension; see module docstring)
# ---------------------------------------------------------------------------
def victim_ways(state: TagState) -> int:
    """Entries per array in the victim tag buffer (0 = disabled)."""
    return state["vtags"].shape[-1]


def victim_probe(state: TagState, array_idx: jnp.ndarray,
                 addr: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fully-associative lookup in each request's victim buffer.

    Returns (hit, slot). A zero-sized buffer never hits.
    """
    R = array_idx.shape[0]
    if victim_ways(state) == 0:
        return jnp.zeros((R,), bool), jnp.zeros((R,), jnp.int32)
    vtags = state["vtags"][array_idx]            # (R, V)
    vvalid = state["vvalid"][array_idx]
    match = (vtags == addr[:, None]) & vvalid
    hit = match.any(axis=-1)
    slot = jnp.argmax(match, axis=-1).astype(jnp.int32)
    return hit, slot


def victim_invalidate(state: TagState, array_idx: jnp.ndarray,
                      slot: jnp.ndarray, mask: jnp.ndarray) -> TagState:
    """Drop masked requests' victim entries (e.g. on promote back to L1)."""
    if victim_ways(state) == 0:
        return state
    a = jnp.where(mask, array_idx, state["vtags"].shape[0])
    return dict(state, vvalid=state["vvalid"].at[a, slot].set(
        False, mode="drop"))


def victim_insert(state: TagState, array_idx: jnp.ndarray,
                  addr: jnp.ndarray, now, mask: jnp.ndarray) -> TagState:
    """FIFO-install masked requests' tags into their victim buffers.

    Invalid slots win first, then the oldest install. Duplicate
    (array, slot) targets resolve last-writer-wins, like ``fill`` — a
    round that evicts several lines from one cache keeps only the last
    (the buffer has one fill port).
    """
    if victim_ways(state) == 0:
        return state
    int_min = jnp.iinfo(jnp.int32).min
    vvalid = state["vvalid"][array_idx]          # (R, V)
    vborn = state["vborn"][array_idx]
    slot = jnp.argmin(jnp.where(vvalid, vborn, int_min),
                      axis=-1).astype(jnp.int32)
    a = jnp.where(mask, array_idx, state["vtags"].shape[0])
    return dict(
        state,
        vtags=state["vtags"].at[a, slot].set(addr, mode="drop"),
        vvalid=state["vvalid"].at[a, slot].set(True, mode="drop"),
        vborn=state["vborn"].at[a, slot].set(now, mode="drop"))
