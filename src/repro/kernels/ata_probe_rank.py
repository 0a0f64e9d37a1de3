"""Fused probe+rank+arbitrate for the ATA round loop, as a Pallas kernel.

The paper's Fig. 6 structure is one *parallel* pass: a batch of request
tags is compared against every cluster tag array at once, the per-set
winners are selected, and the remote data port arbitrates among the
known remote hits. The simulator's lax round loop used to materialize
that as a chain of separate ops (``tagarray.probe_many`` →
``contention.group_rank`` → arbitration masks); this kernel is the
whole chain in one VMEM-resident pass per request tile:

  grid (R/BR,): each program holds BR requests plus the *complete* tag
  state resident in VMEM (tags + valid/dirty of every cache, way-major
  as (W, S, C) — e.g. the paper geometry's 30x8x64 arrays are ~120KB
  total). Per tile it runs

    1. the tag selector and comparator group of
       :func:`repro.kernels.ata_tag_probe.first_match` (a select over
       the S sets and a first-hit scan over the W ways, every
       intermediate a 2-D (BR, C) int32 plane),
    2. per-set winner ranking (self-hit / first-peer selection over the
       cluster slice of the (BR, C) hit matrix, a masked ``min`` over a
       cache-id iota), and
    3. service-port arbitration: the queue position of each winning
       remote hit at its serving cache's data port. Within a tile the
       exclusive prefix count is a strictly-lower-triangular (BR, BR)
       contraction on the MXU (0/1 operands, exact); ranks compose
       across tiles through a resident output accumulator — the TPU
       grid is sequential, so tile *i*'s ranks start where tile
       *i-1*'s per-cache counts left off, exactly like the stable
       sort/segment-sum path of :func:`repro.core.contention.group_rank`.

The per-port *group totals* (occupancy needs them) are only known once
every tile has run; the kernel therefore emits the final per-cache
count vector as its last output (the sequential grid revisits one
block) and the wrapper gathers ``counts[src_cache]`` — one (R,) gather
outside the kernel, everything else fused.

Per-request vectors travel as (R, 1) int32 columns. Requests whose
count does not tile by BR are padded with dead lanes (``live=0``) that
hit nothing and rank nowhere, so any R works.

``interpret=False`` (the default) compiles with Mosaic and needs a TPU;
``interpret=True`` interprets the same body on any backend. Asking for
the compiled kernel without a TPU raises.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ata_tag_probe import (column, first_match, require_tpu,
                                         way_major)

DEFAULT_BR = 128   # requests per program


def _any(x):
    """Lane-axis ``any`` of a (BR, C) bool plane as a (BR, 1) column."""
    return jnp.max(x.astype(jnp.int32), axis=-1, keepdims=True) > 0


def _probe_rank_kernel(set_ref, qtag_ref, core_ref, cbase_ref, live_ref,
                       deny_ref, tags_ref, state_ref,
                       local_ref, way_ref, rok_ref, src_ref, rank_ref,
                       counts_ref, *, cluster_size: int):
    core = core_ref[...]                     # (BR, 1) self cache id
    cbase = cbase_ref[...]                   # (BR, 1) first cache of cluster
    live = live_ref[...] > 0                 # (BR, 1) padding mask
    deny = deny_ref[...] > 0                 # (BR, 1) writes / prefilter hits
    W, _, C = tags_ref.shape
    BR = core.shape[0]

    # the per-cache port counters carried across the sequential grid
    @pl.when(pl.program_id(0) == 0)
    def _():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    # 1. tag selector + comparator group over every cache
    way_c, dirty_c = first_match(set_ref[...], qtag_ref[...], tags_ref,
                                 state_ref)          # (BR, C) each
    hit_c = way_c < W

    # 2. per-set winner ranking over the cluster slice
    cid = jax.lax.broadcasted_iota(jnp.int32, (BR, C), 1)
    is_self = cid == core
    in_cluster = (cid >= cbase) & (cid < cbase + cluster_size)
    local_hit = _any(hit_c & is_self) & live
    # one-hot contraction == take_along_axis at the self slot
    hit_way = jnp.sum(jnp.where(is_self & hit_c, way_c, 0), axis=-1,
                      keepdims=True)

    rmask = hit_c & in_cluster & ~is_self            # (BR, C)
    any_remote = _any(rmask)
    # first hitting peer (lowest cache id == lowest cluster slot)
    src = jnp.min(jnp.where(rmask, cid, C), axis=-1, keepdims=True)
    src_cache = jnp.where(any_remote, src, cbase)
    first = rmask & (cid == src_cache)
    src_dirty = _any(first & (dirty_c > 0))
    remote_ok = live & ~deny & ~local_hit & any_remote & ~src_dirty

    # 3. service-port arbitration: queue position at the serving cache's
    # data port — within-tile exclusive prefix (strictly lower
    # triangular contraction), offset by the earlier tiles' counts.
    oh = jnp.where(remote_ok & (cid == src_cache), 1, 0)   # (BR, C)
    row = jax.lax.broadcasted_iota(jnp.int32, (BR, BR), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (BR, BR), 1)
    lower = jnp.where(col < row, 1.0, 0.0).astype(jnp.bfloat16)
    within = jnp.dot(lower, oh.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32).astype(jnp.int32)
    carried = counts_ref[...]                        # (1, C)
    prank = jnp.sum((within + carried) * oh, axis=-1, keepdims=True)
    counts_ref[...] = carried + jnp.sum(oh, axis=0, keepdims=True)

    local_ref[...] = local_hit.astype(jnp.int32)
    way_ref[...] = hit_way
    rok_ref[...] = remote_ok.astype(jnp.int32)
    src_ref[...] = src_cache
    rank_ref[...] = prank


@functools.partial(jax.jit,
                   static_argnames=("cluster_size", "br", "interpret"))
def _probe_rank_call(set_idx, qtag, core, cbase, live, deny, tags, state,
                     *, cluster_size: int, br: int, interpret: bool):
    R = set_idx.shape[0]
    W, S, C = tags.shape
    col = pl.BlockSpec((br, 1), lambda i: (i, 0))       # request tiles
    whole = pl.BlockSpec((W, S, C), lambda i: (0, 0, 0))  # resident state
    return pl.pallas_call(
        functools.partial(_probe_rank_kernel, cluster_size=cluster_size),
        grid=(R // br,),
        in_specs=[col] * 6 + [whole] * 2,
        out_specs=[col] * 5 + [pl.BlockSpec((1, C), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((R, 1), jnp.int32)] * 5
        + [jax.ShapeDtypeStruct((1, C), jnp.int32)],   # final port counts
        interpret=interpret,
    )(set_idx, qtag, core, cbase, live, deny, tags, state)


def ata_probe_rank(set_idx, qtag, core, cluster_base, deny, tags, valid,
                   dirty, *, cluster_size: int, br: int = DEFAULT_BR,
                   interpret: bool = False):
    """Fused probe + per-set winner ranking + port arbitration.

    set_idx      : (R,) int32  L1 set selected by each request
    qtag         : (R,) int32  request line address (the compared tag)
    core         : (R,) int32  issuing core's cache id
    cluster_base : (R,) int32  first cache id of the issuing cluster
    deny         : (R,) bool   excluded from remote service (writes,
                               victim-prefilter hits)
    tags/valid/dirty : (C, S, W) the full aggregated tag state
    cluster_size : static aggregation breadth G

    Returns (local_hit (R,) bool, hit_way (R,) int32 — the self-array
    way, meaningful where ``local_hit`` — remote_ok (R,) bool,
    src_cache (R,) int32 — serving peer, meaningful where ``remote_ok``
    — prank (R,) int32, psize (R,) int32). ``prank``/``psize`` are the
    queue position and group size at the serving cache's data port,
    bit-identical to ``contention.group_rank(src_cache, remote_ok,
    C)``.

    R not divisible by ``br`` is padded internally with dead lanes.
    ``interpret`` selects the interpreter; the default compiled kernel
    raises off-TPU.
    """
    if not interpret:
        require_tpu("ata_probe_rank")
    R = set_idx.shape[0]
    # whole sublane tiles: an (R, 1) int32 column tiles by 8 rows
    br = min(br, -(-max(R, 1) // 8) * 8)
    pad = (-R) % br
    args = [column(a) for a in (set_idx, qtag, core, cluster_base,
                                jnp.ones((R,), jnp.int32), deny)]
    if pad:
        args = [jnp.pad(a, ((0, pad), (0, 0))) for a in args]
    state = (jnp.asarray(valid, jnp.int32)
             | (jnp.asarray(dirty, jnp.int32) << 1))
    local, way, rok, src, rank, counts = _probe_rank_call(
        *args, way_major(tags), way_major(state),
        cluster_size=cluster_size, br=br, interpret=interpret)
    local, way, rok, src, rank = (x[:R, 0] for x in
                                  (local, way, rok, src, rank))
    remote_ok = rok.astype(bool)
    psize = jnp.where(remote_ok, counts[0][src], 0)
    return (local.astype(bool), way, remote_ok, src, rank, psize)
