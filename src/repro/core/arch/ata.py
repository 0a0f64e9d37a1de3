"""ATA: aggregated tag array probed in parallel at zero added latency.

Only *known* remote hits cross the crossbar; writes are local-only with
dirty-bit L2 diversion [the paper's coherence rule]. The tag-side
filtering — no probe traffic, no speculative data movement — is the
paper's core contention win.
"""
from __future__ import annotations

import dataclasses
from typing import ClassVar

import jax
import jax.numpy as jnp

from repro.core import tagarray
from repro.core.arch.base import TAG_CHECK, ArchPolicy, L1Outcome, RequestBatch
from repro.core.geometry import GpuGeometry
from repro.core.probe import fused_probe_rank


@dataclasses.dataclass(frozen=True)
class AtaPolicy(ArchPolicy):
    name: str = "ata"
    fills_own_core: ClassVar[bool] = True
    sectored: ClassVar[bool] = True

    @property
    def stack_key(self) -> str:
        # The whole ATA family (base, FIFO replacement, CIAO-style
        # bypass) shares one round dataflow, so sweeps stack the
        # variants into a single executable behind a traced policy
        # index.
        return "ata"

    def _victim_prefilter(self, l1: tagarray.TagState, reqs: RequestBatch):
        """Hook: mask of requests a victim structure can serve locally.

        Probed on L1 miss *before* the remote path — a hit here is
        served inside the core's own L1 complex (one extra sequential
        tag check) and never enters the remote-port contention group or
        crosses the crossbar. The base policy has no victim structure:
        ``None`` keeps the stage's computation graph untouched.
        """
        return None

    def l1_stage(self, geom: GpuGeometry, l1: tagarray.TagState,
                 reqs: RequestBatch, t, *,
                 backend: str = "lax") -> L1Outcome:
        addr, set_idx = reqs.addr, reqs.set_idx
        # victim prefilter: read misses served by a victim structure
        # (when the subclass provides one) skip the remote path.
        pre = self._victim_prefilter(l1, reqs)
        # aggregated tag array: all cluster tags compared in parallel,
        # zero added latency, zero probe traffic — plus winner pick and
        # remote-port arbitration, fused under the selected backend
        # (repro.core.probe; all backends are bit-exact).
        pr = fused_probe_rank(geom, l1, reqs, pre_served=pre,
                              replacement=self.replacement,
                              backend=backend)
        local_hit, way = pr.local_hit, pr.touch_way
        remote_ok, src_cache = pr.remote_ok, pr.src_cache
        prank, psize = pr.prank, pr.psize
        vserved = (None if pre is None
                   else pre & ~local_hit & ~reqs.is_write)
        # only *actual* remote hits occupy the remote data port — the
        # filtering that is the paper's core contention win.
        occupancy = jnp.where(
            remote_ok, psize.astype(jnp.float32) * geom.svc_port, 0.0)
        served = local_hit | remote_ok
        local_hits = local_hit
        l1_time = jnp.where(
            local_hit, geom.lat_l1 * 1.0,
            jnp.where(remote_ok,
                      geom.lat_l1 + geom.lat_xbar
                      + prank.astype(jnp.float32) * geom.svc_port,
                      float(TAG_CHECK)))
        if vserved is not None:
            served = served | vserved
            local_hits = local_hits | vserved
            l1_time = jnp.where(vserved,
                                geom.lat_l1 + float(TAG_CHECK), l1_time)
        l1 = tagarray.touch_rows(l1, set_idx, way, t, local_hit,
                                 set_dirty=reqs.is_write)
        if pr.need is None:
            noc_flits = jnp.sum(remote_ok) * geom.flits_per_line
            req_flits = remote_ok * (geom.flits_per_line * 1.0)
        else:
            # a peer sends the sectors the requester needs
            req_flits = (jnp.where(remote_ok,
                                   jax.lax.population_count(pr.need), 0)
                         * (geom.flits_per_line / geom.sectors_per_line))
            noc_flits = jnp.sum(req_flits)
        return L1Outcome(
            l1=l1,
            served=served,
            l1_time=l1_time,
            go_l2=~served,
            pre_l2=jnp.full((reqs.n_requests,), float(TAG_CHECK)),
            occupancy=occupancy,
            fill_cache=reqs.core,
            fill_set=set_idx,
            local_hits=local_hits,
            remote_hits=remote_ok,
            noc_flits=noc_flits,
            # only known remote hits put flits on the interconnect —
            # the tag-side filtering that is the paper's core win
            noc_src=jnp.where(remote_ok, src_cache, reqs.core),
            noc_req_flits=req_flits,
            need=pr.need,
            sector_misses=pr.sector_miss,
        )
