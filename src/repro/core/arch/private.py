"""Baseline architecture: per-core private L1, misses go straight to L2."""
from __future__ import annotations

import dataclasses
from typing import ClassVar

import jax
import jax.numpy as jnp

from repro.core import tagarray
from repro.core.arch.base import TAG_CHECK, ArchPolicy, L1Outcome, RequestBatch
from repro.core.geometry import GpuGeometry


@dataclasses.dataclass(frozen=True)
class PrivatePolicy(ArchPolicy):
    name: str = "private"
    fills_own_core: ClassVar[bool] = True
    sectored: ClassVar[bool] = True

    def l1_stage(self, geom: GpuGeometry, l1: tagarray.TagState,
                 reqs: RequestBatch, t, *,
                 backend: str = "lax") -> L1Outcome:
        del backend   # no probe chain to lower (ATA-family axis)
        R = reqs.n_requests
        hit, way, _ = tagarray.probe(l1, reqs.core, reqs.set_idx, reqs.addr,
                                     policy=self.replacement)
        need = sector_misses = None
        if reqs.sect is not None:
            # a hit needs every requested sector of the line
            with jax.named_scope(tagarray.SECTOR_SCOPE):
                need = jnp.where(
                    hit, reqs.sect & ~tagarray.sectors_at(
                        l1, reqs.core, reqs.set_idx, way), reqs.sect)
                sector_misses = hit & (need != 0)
                hit = need == 0
        l1 = tagarray.touch_rows(l1, reqs.set_idx, way, t, hit,
                                 set_dirty=reqs.is_write)
        return L1Outcome(
            l1=l1,
            served=hit,
            l1_time=jnp.where(hit, geom.lat_l1 * 1.0, float(TAG_CHECK)),
            go_l2=~hit,
            pre_l2=jnp.full((R,), float(TAG_CHECK)),
            occupancy=jnp.zeros((R,), jnp.float32),
            fill_cache=reqs.core,
            fill_set=reqs.set_idx,
            local_hits=hit,
            remote_hits=jnp.zeros((R,), bool),
            noc_flits=0.0,
            need=need,
            sector_misses=sector_misses,
        )
