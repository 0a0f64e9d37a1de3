"""Architecture-policy interface for the cache-hierarchy simulator.

The simulator is a pipeline of stages; only the first — the L1 complex —
differs between contention-mitigation architectures:

    L1 policy stage  ->  shared L2 stage  ->  L1 fill stage  ->  timing

An :class:`ArchPolicy` implements the L1 stage: given the per-round
request batch and the L1 tag state, it decides which requests are served
inside the L1 complex, at what latency, with what serial-resource
occupancy, and where misses fill on return. Everything downstream
(L2 queueing, DRAM, fill, warp-timing) is policy-independent and lives
in ``repro.core.simulator``.

New architectures subclass :class:`ArchPolicy`, implement ``l1_stage``,
and register themselves with :func:`repro.core.arch.register_arch` — no
core edits required.
"""
from __future__ import annotations

import dataclasses
from typing import ClassVar, NamedTuple, Optional, Union

import jax.numpy as jnp

from repro.core import tagarray
from repro.core.geometry import GpuGeometry
from repro.core.tagarray import ReplacementPolicy

#: Cycles to detect an L1 miss (tag check before dispatching onwards).
TAG_CHECK = 8


class RequestBatch(NamedTuple):
    """One round's flattened requests plus derived routing indices.

    R = n_cores * m requests, core-major: request ``c * m + j`` is core
    ``c``'s ``j``-th, so an ``(R,)`` field is ``(n_cores, m)`` rows and
    updates of each core's own L1 take ``tagarray``'s row form.
    G = cluster size.
    """
    addr: jnp.ndarray        # (R,) int32 line addresses
    is_write: jnp.ndarray    # (R,) bool
    core: jnp.ndarray        # (R,) int32 issuing core
    cluster: jnp.ndarray     # (R,) int32 cluster of the issuing core
    self_slot: jnp.ndarray   # (R,) int32 core's slot within its cluster
    set_idx: jnp.ndarray     # (R,) int32 local L1 set of addr
    bank: jnp.ndarray        # (R,) int32 local L1 bank of addr
    peers: jnp.ndarray       # (R, G) int32 cache ids of the whole cluster
    #: (R,) int32 sectors the request asks for on a sectored geometry;
    #: None where lines are whole
    sect: Optional[jnp.ndarray] = None

    @property
    def n_requests(self) -> int:
        return self.addr.shape[0]


class L1Outcome(NamedTuple):
    """What the L1 complex did with the round's requests.

    Every field is (R,) unless noted. ``noc_flits`` is the scalar NoC
    traffic the policy itself generated (probes, peer transfers);
    downstream stages add L2/write-back traffic on top.
    """
    l1: tagarray.TagState           # post-probe/touch L1 tag state
    served: jnp.ndarray             # request completed inside L1 complex
    l1_time: jnp.ndarray            # float32 completion time if served
    go_l2: jnp.ndarray              # request continues to L2
    pre_l2: jnp.ndarray             # float32 cycles spent before L2 dispatch
    occupancy: jnp.ndarray          # float32 serial-resource busy time
    fill_cache: jnp.ndarray         # int32 tag array to fill on return
    fill_set: jnp.ndarray           # int32 set to fill on return
    local_hits: jnp.ndarray         # bool, for hit-rate accounting
    remote_hits: jnp.ndarray        # bool, served by a peer L1
    noc_flits: Union[jnp.ndarray, float]  # scalar flit count this round
    bypass_fill: Optional[jnp.ndarray] = None  # bool; True = skip L1 fill
    #: (R,) int32 core whose cache serves each request (the NoC source
    #: for remote transfers); None = the requesting core itself.
    noc_src: Optional[jnp.ndarray] = None
    #: (R,) float32 probe + data flits each request puts on the
    #: L1-complex interconnect (``repro.core.noc``); None = the default
    #: ``remote_hits * flits_per_line``. L2/write-back traffic rides
    #: the memory-side network and is *not* included here.
    noc_req_flits: Optional[jnp.ndarray] = None
    #: Sectored geometries only (None where lines are whole): (R,) int32
    #: sectors each request needs from a peer or the L2 and fills into
    #: its L1 (its requested sectors the own L1 lacks), and (R,) bool
    #: tag hits in the own L1 that lack a requested sector.
    need: Optional[jnp.ndarray] = None
    sector_misses: Optional[jnp.ndarray] = None


@dataclasses.dataclass(frozen=True)
class ArchPolicy:
    """A pluggable L1-complex architecture.

    ``replacement`` selects the victim scheme the policy's tag probes and
    the shared fill stage use for this architecture's L1 arrays (the L2
    always runs LRU).

    ``victim_ways`` / ``track_thrash`` declare the policy's TagState
    extensions (victim tag buffer entries per cache, per-core thrash
    counters). The simulator sizes the L1 state by the *maximum* over a
    dataflow group, so a policy that declares an extension can stack
    with family members that ignore it: the extension arrays are
    zero-sized when nobody asks for them (existing goldens stay
    bit-exact) and dead weight in the branches that do not read them.

    ``fills_own_core`` declares that the policy's ``fill_cache`` is
    always ``reqs.core``, the requester's own L1: the shared fill stage
    then updates the L1 with ``tagarray.fill_rows`` instead of a
    scatter. A dataflow property of the class, not a knob.

    ``sectored`` declares that the policy models sectored lines
    (``GpuGeometry.sectors_per_line > 1``): a hit needs every requested
    sector (``RequestBatch.sect``) and the outcome reports ``need`` and
    ``sector_misses``. The simulator refuses a sectored geometry for
    any other policy.
    """
    fills_own_core: ClassVar[bool] = False
    sectored: ClassVar[bool] = False

    name: str
    replacement: ReplacementPolicy = ReplacementPolicy.LRU
    victim_ways: int = 0
    track_thrash: bool = False

    @property
    def stack_key(self) -> str:
        """Dataflow-group tag for sweep stacking.

        Architectures that return the same ``stack_key`` declare an
        identical dataflow shape (same tag-state layout, same output
        pytree per round), so ``repro.core.sweep`` may compile them into
        one vmapped executable and select the active policy per grid
        point with a traced index. The default — the policy's own name —
        opts out of cross-policy stacking; families of variants (e.g.
        the ATA replacement/bypass variants) override it to share.
        """
        return self.name

    def l1_stage(self, geom: GpuGeometry, l1: tagarray.TagState,
                 reqs: RequestBatch, t: jnp.ndarray, *,
                 backend: str = "lax") -> L1Outcome:
        """Run the policy's L1 complex over one round's requests.

        ``backend`` selects the probe lowering (``repro.core.probe``) —
        a *static* simulator axis threaded down from
        ``simulate(..., probe_backend=...)``. Only the ATA family has a
        probe chain to lower; policies without one accept and ignore
        the keyword (backend choice never changes any policy's results
        — tier-1 tested).
        """
        raise NotImplementedError
