"""ATA + per-core victim tag buffer (shared-resource survey, arXiv
1803.06958 §victim/insertion variants).

A small fully-associative FIFO buffer next to each L1 keeps the tags of
recently evicted lines. On an L1 miss it is probed *before* the
remote/aggregated path (the ``_victim_prefilter`` hook in
:class:`~repro.core.arch.ata.AtaPolicy`): a read that hits a victim
entry is served inside the core's own L1 complex — one extra sequential
tag check (:data:`~repro.core.arch.base.TAG_CHECK` cycles) on top of
the L1 latency — and never enters the remote-port contention group or
crosses the crossbar, even when a peer copy exists. The hit line is
promoted back into the L1 proper, its buffer entry invalidated and
swapped with whatever the promotion evicted. Misses past the buffer
behave exactly like the base ATA policy, and writes keep the paper's
local-only coherence rule (they never hit the buffer).

Entries come from evictions: the policy predicts the shared fill
stage's replacement decision (the same ``probe`` the fill stage runs on
the returned state) and captures the outgoing valid tags. Within a
round, duplicate evictions from one cache resolve last-writer-wins —
the buffer has a single fill port (see ``tagarray.victim_insert``).

``victim_ways=0`` disables the buffer; the policy is then bit-exact
with :class:`~repro.core.arch.ata.AtaPolicy` (a hypothesis test asserts
this). ``stack_key`` is inherited — ``"ata"`` — so the whole ATA family
plus this variant compiles into one stacked executable.
"""
from __future__ import annotations

import dataclasses
from typing import ClassVar

import jax.numpy as jnp

from repro.core import tagarray
from repro.core.arch.ata import AtaPolicy
from repro.core.arch.base import L1Outcome, RequestBatch
from repro.core.geometry import GpuGeometry


@dataclasses.dataclass(frozen=True)
class VictimPolicy(AtaPolicy):
    name: str = "victim"
    sectored: ClassVar[bool] = False   # whole lines only
    victim_ways: int = 8

    def _victim_prefilter(self, l1: tagarray.TagState, reqs: RequestBatch):
        if tagarray.victim_ways(l1) == 0:
            return None
        hit, _ = tagarray.victim_probe(l1, reqs.core, reqs.addr)
        return hit

    def l1_stage(self, geom: GpuGeometry, l1: tagarray.TagState,
                 reqs: RequestBatch, t, *,
                 backend: str = "lax") -> L1Outcome:
        out = super().l1_stage(geom, l1, reqs, t, backend=backend)
        if tagarray.victim_ways(out.l1) == 0:
            return out
        addr, set_idx = reqs.addr, reqs.set_idx
        state = out.l1

        # Reconstruct the base stage's victim-served mask. ``touch``
        # only moves timestamps/dirty bits, so probing tags on the
        # returned state reproduces the pre-touch local-hit mask, and
        # the buffer arrays were untouched entirely.
        hits, _, _ = tagarray.probe_many(state, reqs.peers, set_idx, addr)
        is_self = (jnp.arange(geom.cluster_size)[None, :]
                   == reqs.self_slot[:, None])
        local_hit = (hits & is_self).any(axis=-1)
        vhit, vslot = tagarray.victim_probe(state, reqs.core, addr)
        vserved = vhit & ~local_hit & ~reqs.is_write

        # Promote back into the L1 proper: the entry leaves the buffer
        # and swaps with the line the promotion evicts.
        state = tagarray.victim_invalidate(state, reqs.core, vslot, vserved)
        _, pway, _ = tagarray.probe(state, reqs.core, set_idx, addr,
                                    policy=self.replacement)
        swap_tag = state["tags"][reqs.core, set_idx, pway]
        swap_valid = state["valid"][reqs.core, set_idx, pway]
        state, promo_wb = tagarray.fill(state, reqs.core, set_idx, pway,
                                        addr, t, vserved)
        state = tagarray.victim_insert(state, reqs.core, swap_tag, t,
                                       vserved & swap_valid)

        # Capture what the shared fill stage will evict on L2/remote
        # returns. It probes the state we return, so predicting its
        # victim way here is exact (up to same-(cache,set) duplicates
        # within the round, which resolve last-writer-wins there too).
        fill_mask = out.go_l2 | out.remote_hits
        if out.bypass_fill is not None:
            fill_mask = fill_mask & ~out.bypass_fill
        _, fway, _ = tagarray.probe(state, out.fill_cache, out.fill_set,
                                    addr, policy=self.replacement)
        ev_tag = state["tags"][out.fill_cache, out.fill_set, fway]
        ev_valid = state["valid"][out.fill_cache, out.fill_set, fway]
        state = tagarray.victim_insert(state, out.fill_cache, ev_tag, t,
                                       fill_mask & ev_valid)

        return out._replace(
            l1=state,
            # promotions of a dirty victim's frame write the old line back
            noc_flits=out.noc_flits
            + jnp.sum(promo_wb) * geom.flits_per_line,
        )
