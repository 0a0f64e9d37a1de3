"""Remote sharing: broadcast probes to cluster peers [Dublish'16, Ibrahim'19].

A local miss queries every peer L1 in the cluster; the probe service
queue and NoC load delay sit on the critical path even when the line
ends up coming from L2.
"""
from __future__ import annotations

import dataclasses
from typing import ClassVar

import jax.numpy as jnp

from repro.core import tagarray
from repro.core.arch.base import TAG_CHECK, ArchPolicy, L1Outcome, RequestBatch
from repro.core.contention import group_rank
from repro.core.geometry import GpuGeometry


@dataclasses.dataclass(frozen=True)
class RemotePolicy(ArchPolicy):
    name: str = "remote"
    fills_own_core: ClassVar[bool] = True

    def l1_stage(self, geom: GpuGeometry, l1: tagarray.TagState,
                 reqs: RequestBatch, t, *,
                 backend: str = "lax") -> L1Outcome:
        del backend   # no probe chain to lower (ATA-family axis)
        addr, set_idx = reqs.addr, reqs.set_idx
        hit, way, _ = tagarray.probe(l1, reqs.core, set_idx, addr,
                                     policy=self.replacement)
        miss = ~hit
        # broadcast probes: each miss queries all peers; probe service
        # queue per cluster + NoC load delay sit on the critical path.
        rank, n_miss = group_rank(reqs.cluster, miss, geom.n_clusters)
        probe_flits = n_miss.astype(jnp.float32) * (geom.cluster_size - 1)
        noc_delay = probe_flits / geom.noc_bw
        probe_wait = (geom.lat_probe + rank.astype(jnp.float32)
                      * geom.svc_probe + noc_delay)
        rhits, _, _ = tagarray.probe_many(l1, reqs.peers, set_idx, addr)
        rhits = rhits & (jnp.arange(geom.cluster_size)[None, :]
                         != reqs.self_slot[:, None])
        remote_hit = miss & rhits.any(axis=-1)
        src_slot = jnp.argmax(rhits, axis=-1)
        src_cache = reqs.cluster * geom.cluster_size + src_slot
        prank, psize = group_rank(src_cache, remote_hit, geom.n_cores)
        xfer = geom.lat_xbar + prank.astype(jnp.float32) * geom.svc_port
        # every peer cache's tag port serves every probe in the cluster
        occupancy = jnp.where(
            miss, n_miss.astype(jnp.float32) * geom.svc_probe, 0.0)
        occupancy = jnp.maximum(
            occupancy,
            jnp.where(remote_hit,
                      psize.astype(jnp.float32) * geom.svc_port, 0.0))
        l1 = tagarray.touch_rows(l1, set_idx, way, t, hit,
                                 set_dirty=reqs.is_write)
        return L1Outcome(
            l1=l1,
            served=hit | remote_hit,
            l1_time=jnp.where(hit, geom.lat_l1 * 1.0,
                              TAG_CHECK + probe_wait
                              + jnp.where(remote_hit, xfer, 0.0)),
            go_l2=miss & ~remote_hit,
            pre_l2=TAG_CHECK + probe_wait,   # probes extend the L2 path
            occupancy=occupancy,
            fill_cache=reqs.core,
            fill_set=set_idx,
            local_hits=hit,
            remote_hits=remote_hit,
            noc_flits=(jnp.sum(miss) * (geom.cluster_size - 1)
                       + jnp.sum(remote_hit) * geom.flits_per_line),
            # Topology models see only the point-to-point *data*
            # transfers (line from the serving peer). The broadcast
            # probes are already priced inside this policy
            # (noc_delay/probe_wait above) and ride the dedicated probe
            # channels — routing them through the data network too
            # would double-charge them, and only on hits.
            noc_src=jnp.where(remote_hit, src_cache, reqs.core),
            noc_req_flits=remote_hit * (geom.flits_per_line * 1.0),
        )
