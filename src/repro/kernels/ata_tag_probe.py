"""Aggregated-tag-array probe as a Pallas TPU kernel.

The paper's hardware structure (Fig. 6): a batch of request address tags
is compared against the tag arrays of *all* caches in a cluster in
parallel; per (request, cache) the kernel reports hit and hit-way. On a
GPU this is SRAM banks + tag selectors + comparator groups; on TPU we
re-tile it for VMEM/VPU:

  grid (R/BR,): each program holds BR requests and the tag arrays of
  all C caches resident in VMEM, laid out way-major as (W, S, C) so
  every per-way slab is a 2-D (sets x caches) tile. The "tag selector"
  (route each set's tags to the comparators of the requests that
  selected it) becomes a select over the S sets per way, and the
  "comparator group" an equality over the (BR, C) request x cache
  plane; a loop over the W ways keeps the first hitting way. Every
  intermediate is a 2-D int32 (BR, C) array — requests on sublanes,
  caches on lanes — which is what Mosaic lowers.

Selects (not jnp.take) keep the int32 tag path exact and avoid
dynamic-gather lowering restrictions in Mosaic.

``interpret=False`` (the default) compiles the kernel with Mosaic and
needs a TPU; ``interpret=True`` interprets the same body on any backend
(the CPU validation path). Asking for the compiled kernel without a TPU
raises.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BR = 128   # requests per program


def require_tpu(kernel: str) -> None:
    """Raise unless the default backend can run a Mosaic kernel."""
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"{kernel}: the compiled Pallas kernel needs a TPU, but the "
            f"default backend is {backend!r}; use the interpreted "
            "kernel (interpret=True / the *_interpret backend) instead")


def first_match(sets, qtag, tags_ref, state_ref):
    """First hitting way and dirty-hit flag per (request, cache).

    sets, qtag : (BR, 1) int32 request columns
    tags_ref   : (W, S, C) int32 tags, way-major
    state_ref  : (W, S, C) int32 line state (bit 0 valid, bit 1 dirty)
    returns (way (BR, C) int32 — the lowest hitting way, W where none —
    dirty (BR, C) int32 — 1 where some hitting way is dirty).
    """
    W, S, C = tags_ref.shape
    shape = (sets.shape[0], C)

    def per_way(w, carry):
        way, dirty = carry
        t_w = tags_ref[w]                           # (S, C)
        st_w = state_ref[w]
        g_t = jnp.zeros(shape, jnp.int32)
        g_st = jnp.zeros(shape, jnp.int32)
        for s in range(S):                          # tag selector
            in_set = sets == s                      # (BR, 1)
            g_t = jnp.where(in_set, t_w[s:s + 1, :], g_t)
            g_st = jnp.where(in_set, st_w[s:s + 1, :], g_st)
        match = (g_t == qtag) & ((g_st & 1) != 0)   # comparator group
        way = jnp.where(match & (way == W), w, way)
        dirty = jnp.where(match & ((g_st & 2) != 0), 1, dirty)
        return way, dirty

    return jax.lax.fori_loop(
        0, W, per_way, (jnp.full(shape, W, jnp.int32),
                        jnp.zeros(shape, jnp.int32)))


def way_major(x) -> jax.Array:
    """(C, S, W) -> the kernels' (W, S, C) int32 layout."""
    return jnp.transpose(jnp.asarray(x, jnp.int32), (2, 1, 0))


def column(x) -> jax.Array:
    """(R,) -> an (R, 1) int32 request column."""
    return jnp.asarray(x, jnp.int32).reshape(-1, 1)


def _probe_kernel(set_ref, qtag_ref, tags_ref, valid_ref,
                  hits_ref, ways_ref):
    W = tags_ref.shape[0]
    way, _ = first_match(set_ref[...], qtag_ref[...], tags_ref, valid_ref)
    hit = way < W
    hits_ref[...] = hit.astype(jnp.int32)
    ways_ref[...] = jnp.where(hit, way, 0)


@functools.partial(jax.jit, static_argnames=("br", "interpret"))
def _ata_tag_probe_call(set_col, qtag_col, tags_t, valid_t, *, br: int,
                        interpret: bool):
    R = set_col.shape[0]
    W, S, C = tags_t.shape
    if R % br:
        raise ValueError(f"R={R} must tile by br={br}")
    col = pl.BlockSpec((br, 1), lambda i: (i, 0))
    state = pl.BlockSpec((W, S, C), lambda i: (0, 0, 0))   # resident
    out = pl.BlockSpec((br, C), lambda i: (i, 0))
    return pl.pallas_call(
        _probe_kernel,
        grid=(R // br,),
        in_specs=[col, col, state, state],
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct((R, C), jnp.int32)] * 2,
        interpret=interpret,
    )(set_col, qtag_col, tags_t, valid_t)


def ata_tag_probe(set_idx: jax.Array, qtag: jax.Array, tags: jax.Array,
                  valid: jax.Array, *, br: int = DEFAULT_BR,
                  interpret: bool = False):
    """Probe R request tags against C aggregated tag arrays.

    set_idx : (R,) int32   cache set selected by each request
    qtag    : (R,) int32   request address tag
    tags    : (C, S, W) int32 tag arrays of the C caches in the cluster
    valid   : (C, S, W) bool/int8
    returns (hits (R, C) bool, ways (R, C) int32 — the lowest hitting
    way, 0 where no hit)

    ``br`` requests per program; every program holds all C caches.
    ``interpret`` selects the interpreter; the default compiled kernel
    raises off-TPU.
    """
    if not interpret:
        require_tpu("ata_tag_probe")
    R = set_idx.shape[0]
    br = min(br, R)
    hits, ways = _ata_tag_probe_call(
        column(set_idx), column(qtag), way_major(tags),
        way_major(valid), br=br, interpret=interpret)
    return hits.astype(bool), ways
