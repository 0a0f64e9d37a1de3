"""Selectable probe backends for the ATA round loop.

The aggregated-tag-array policies (``repro.core.arch.ata`` and its
family) spend their round in one computation: probe the request batch
against every cluster tag array, pick the per-request winner (self hit,
else first hitting peer), and arbitrate the known remote hits at their
serving caches' data ports. :func:`fused_probe_rank` is that whole
chain as one op with interchangeable lowerings — the **probe backend**,
a *static* axis of the simulator (backends differ structurally, so each
compiles its own executable; contrast the *traced* NoC index, which
switches between same-dataflow models inside one executable):

``lax``
    The default: a fused pure-XLA pass. One ``probe_many`` gather
    feeds hit selection, peer pick, and
    :func:`repro.core.contention.group_rank` arbitration directly.
    Crucially it does *not* run the replacement-victim probe of the
    historical chain: the victim way was only ever consumed by
    ``tagarray.touch`` lanes that the touch itself drops (masked-out
    requests are routed out of bounds), but XLA cannot dead-code it
    because the scatter consumes the way operand for every lane — so
    dropping it here is bit-exact *and* a real rounds/sec win
    (``benchmarks/sim_speed.py`` measures it).
``lax_unfused``
    The historical probe→``group_rank``→arbitrate chain, victim probe
    included, kept as the measured pre-fusion baseline and as the
    executable definition of what the fused paths must reproduce
    bit-exactly.
``pallas``
    The fused Pallas TPU kernel (``repro.kernels.ata_probe_rank``):
    the same chain in one VMEM-resident pass per request tile,
    compiled by Mosaic. TPU only: asking for it on another backend
    raises, it never falls back to the interpreter.
``pallas_interpret``
    The same kernel body interpreted (any backend) — the
    exact-equivalence artifact tier-1 tests pin against ``lax``.

All four return identical integers/booleans (tier-1 tested), so every
committed golden is backend-invariant. The ``lax`` paths also model
sectored lines (``RequestBatch.sect``): a hit, local or remote, needs
every requested sector. The Pallas paths compare whole lines and refuse
sectored requests.

Every backend runs inside the ``jax.named_scope`` :data:`PROBE_SCOPE`
(nested in the round's ``l1`` stage scope), and the Pallas kernel
is named ``ata_probe_rank``, so the probe's device time can be told
apart from the rest of the round. The scope's name is an interface
the benchmark reads.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import tagarray
from repro.core.contention import group_rank
from repro.core.tagarray import ReplacementPolicy

#: The static backend axis, in canonical order.
PROBE_BACKENDS: Tuple[str, ...] = ("lax", "lax_unfused", "pallas",
                                   "pallas_interpret")
DEFAULT_PROBE_BACKEND = "lax"
#: Named scope of the whole probe chain, under every backend.
PROBE_SCOPE = "probe"


def check_probe_backend(backend: str) -> None:
    if backend not in PROBE_BACKENDS:
        raise ValueError(
            f"probe_backend must be one of {PROBE_BACKENDS}, "
            f"got {backend!r}")
    if backend == "pallas":
        from repro.kernels.ata_tag_probe import require_tpu
        require_tpu("probe_backend='pallas'")


class ProbeRank(NamedTuple):
    """The fused chain's outputs, all (R,).

    ``touch_way`` is what the policy hands to ``tagarray.touch`` for
    its local-hit refresh: the self-array hit way where ``local_hit``
    (elsewhere the touch drops the lane, so the value is dead — the
    ``lax_unfused`` backend fills in the historical replacement-victim
    way there, the fused backends do not). ``prank``/``psize`` are the
    queue position and group size at the serving cache's data port,
    exactly ``group_rank(src_cache, remote_ok, n_cores)``.
    """
    local_hit: jnp.ndarray   # bool — hit in the requester's own array
    touch_way: jnp.ndarray   # int32 — way to LRU-touch where local_hit
    remote_ok: jnp.ndarray   # bool — serviceable known remote hit
    src_cache: jnp.ndarray   # int32 — serving peer cache id
    prank: jnp.ndarray       # int32 — position at the serving port
    psize: jnp.ndarray       # int32 — contention group size
    #: sectored geometries only (``lax`` paths): sectors the request
    #: needs from a peer or the L2, and own-array tag hits that lack a
    #: requested sector
    need: Optional[jnp.ndarray] = None
    sector_miss: Optional[jnp.ndarray] = None


def _lax_path(geom, l1: tagarray.TagState, reqs, pre_served,
              replacement: ReplacementPolicy, fused: bool) -> ProbeRank:
    addr, set_idx = reqs.addr, reqs.set_idx
    hits, ways, dirt = tagarray.probe_many(l1, reqs.peers, set_idx, addr)
    is_self = (jnp.arange(geom.cluster_size)[None, :]
               == reqs.self_slot[:, None])
    need = sector_miss = None
    if reqs.sect is not None:
        # a hit, local or remote, needs every requested sector
        with jax.named_scope(tagarray.SECTOR_SCOPE):
            lacking = reqs.sect[:, None] & ~tagarray.sectors_at(
                l1, reqs.peers, set_idx[:, None], ways)
            own_tag_hit = (hits & is_self).any(axis=-1)
            own_lacking = jnp.take_along_axis(
                lacking, reqs.self_slot[:, None], axis=1)[:, 0]
            need = jnp.where(own_tag_hit, own_lacking, reqs.sect)
            sector_miss = own_tag_hit & (own_lacking != 0)
            hits = hits & (lacking == 0)
    local_hit = (hits & is_self).any(axis=-1)
    hit_way = jnp.take_along_axis(ways, reqs.self_slot[:, None],
                                  axis=1)[:, 0]
    if fused:
        touch_way = hit_way
    else:
        # historical chain: the replacement-victim probe whose result is
        # dead where ~local_hit but un-DCE-able behind the touch scatter
        touch_way = jnp.where(
            local_hit, hit_way,
            tagarray.probe(l1, reqs.core, set_idx, addr,
                           policy=replacement)[1])
    rmask = hits & ~is_self
    any_remote = rmask.any(axis=-1)
    src_slot = jnp.argmax(rmask, axis=-1)
    src_cache = reqs.cluster * geom.cluster_size + src_slot
    src_dirty = jnp.take_along_axis(dirt, src_slot[:, None], axis=1)[:, 0]
    # writes are local-only (paper coherence rule); dirty remote copies
    # divert the read to L2; prefilter-served reads skip the port.
    remote_ok = ((~reqs.is_write) & (~local_hit) & any_remote
                 & (~src_dirty))
    if pre_served is not None:
        remote_ok = remote_ok & ~pre_served
    prank, psize = group_rank(src_cache, remote_ok, geom.n_cores)
    return ProbeRank(local_hit, touch_way, remote_ok, src_cache,
                     prank, psize, need, sector_miss)


def _pallas_path(geom, l1: tagarray.TagState, reqs, pre_served,
                 interpret: bool) -> ProbeRank:
    from repro.kernels.ata_probe_rank import ata_probe_rank
    if reqs.sect is not None:
        raise ValueError(
            "the pallas probe backends compare whole lines; sectored "
            "geometries (sectors_per_line > 1) take 'lax' or "
            "'lax_unfused'")
    deny = reqs.is_write
    if pre_served is not None:
        deny = deny | pre_served
    cbase = reqs.cluster * geom.cluster_size
    local_hit, way, remote_ok, src, prank, psize = ata_probe_rank(
        reqs.set_idx, reqs.addr, reqs.core, cbase, deny,
        l1["tags"], l1["valid"], l1["dirty"],
        cluster_size=geom.cluster_size, interpret=interpret)
    return ProbeRank(local_hit, way, remote_ok, src, prank, psize)


def fused_probe_rank(geom, l1: tagarray.TagState, reqs, *,
                     pre_served: Optional[jnp.ndarray] = None,
                     replacement: ReplacementPolicy = ReplacementPolicy.LRU,
                     backend: str = DEFAULT_PROBE_BACKEND) -> ProbeRank:
    """Probe + winner pick + port arbitration under one backend.

    ``pre_served`` (optional (R,) bool) marks requests a victim
    structure will serve locally; they are excluded from the remote
    contention group (``remote_ok & ~pre_served`` — equal to the
    historical ``& ~vserved`` since ``remote_ok`` already excludes
    writes and local hits). ``replacement`` only matters to
    ``lax_unfused``, which reproduces the historical victim probe.
    """
    check_probe_backend(backend)
    with jax.named_scope(PROBE_SCOPE):
        if backend == "lax":
            return _lax_path(geom, l1, reqs, pre_served, replacement, True)
        if backend == "lax_unfused":
            return _lax_path(geom, l1, reqs, pre_served, replacement,
                             False)
        return _pallas_path(geom, l1, reqs, pre_served,
                            interpret=(backend == "pallas_interpret"))
