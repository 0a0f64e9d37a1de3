"""Cache-hierarchy simulator: pluggable L1 policies over shared stages.

One ``lax.scan`` step models one *round*: every core issues ``m`` memory
requests (one coalesced load instruction). A round is a pipeline

    L1 policy stage -> shared L2 stage -> L1 fill stage -> NoC stage
                                                        -> timing

where only the first stage differs between architectures, and the NoC
stage routes the round's remote-probe/remote-data flits through a
pluggable interconnect model (``repro.core.noc``: ``ideal`` — the
default, bit-exact with the pre-NoC simulator — ``crossbar`` with
carried per-port queue backpressure, ``ring`` with hop-distance
latency; per-link occupancy/delay accumulate in the scan carry and
surface as ``SimResult.noc``). The policies live in ``repro.core.arch``
(one module each) and plug in through a registry, so new
contention-mitigation schemes need no edits here:

  private    : local L1 -> L2
  remote     : local L1 -> broadcast probes to cluster peers (NoC queue +
               probe service queue on the critical path) -> remote fetch
               or L2 *after* the probe round-trip  [Dublish'16, Ibrahim'19]
  decoupled  : address-sliced home cache; every request pays the home
               bank-port queue                       [Ibrahim'20/'21]
  ata        : aggregated tag array probed in parallel at zero added
               latency; only *known* remote hits cross the crossbar;
               writes are local-only with dirty-bit L2 diversion  [paper]
  ata_bypass : ata + CIAO-style interference-aware fill bypass
  ata_fifo   : ata under FIFO L1 replacement

Latency composition feeds a warp-level hiding model to produce IPC, and
the L1-complex portion of each request's latency reproduces Fig. 10.

Entry points: :func:`simulate` runs one trace; :func:`simulate_batch`
stacks same-shape traces and ``jax.vmap``s the scanned simulation over
the trace axis, so a whole sweep (all kernels of an app, a parameter
grid) costs one compilation instead of one ``jax.jit`` trace per kernel;
``repro.core.sweep.SweepGrid`` builds on the same core to batch the
*architecture* and *geometry* axes too and shard the stacked axis over
devices.

Multi-tenant traces (``repro.core.trace.mix.WorkloadMix``) carry a
``core_app`` app-id channel and a per-core instruction-intensity
vector; the round accumulates hit/timing counters per app id inside
the scan carry and :func:`_summarize` folds them into
``SimResult.per_app`` (:class:`AppStats`). The app count is the only
new static dimension (:func:`trace_kind`), so same-shape mixes share
executables and solo traces keep exactly their pre-mix ones.

Geometry timing scalars are traced (``GeomScalars``), and a *group* of
same-dataflow architectures is compiled into one executable with the
active policy selected by a traced index (``lax.switch`` over the
per-round step), so an executable is keyed only by
(arch dataflow group, trace shape, geometry structure).
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tagarray
from repro.core.arch import (PAPER_ARCHITECTURES, ArchPolicy, get_arch,
                             registered_archs)
from repro.core.arch.base import TAG_CHECK, RequestBatch
from repro.core.contention import group_rank
from repro.core.geometry import (GEOM_SCALAR_FIELDS, GeomScalars,
                                 GeomStructure, GpuGeometry, PAPER_GEOMETRY,
                                 TracedGeometry, split_geometry)
from repro.core.noc import (NocModel, NocTraffic, get_noc, init_noc_state,
                            registered_nocs)
from repro.core.probe import (PROBE_BACKENDS,
                              check_probe_backend as _check_probe_backend)
from repro.core.telemetry import TelemetryConfig, log2_bucket

#: Backwards-compatible alias: the paper's comparison set. The full,
#: extensible set is ``repro.core.arch.registered_archs()``.
ARCHITECTURES = PAPER_ARCHITECTURES

#: The round's stages, in pipeline order: each is one ``jax.named_scope``
#: in :func:`_round`, so every operation a stage lowers to carries
#: ``.../<stage>/...`` in its HLO ``op_name`` metadata (the aggregated
#: probe nests ``repro.core.probe.PROBE_SCOPE`` inside ``l1``). These
#: names are an interface: the benchmark attributes device time to them.
ROUND_STAGES: Tuple[str, ...] = ("l1", "l2", "fill", "noc", "timing")
_L1, _L2, _FILL, _NOC, _TIMING = ROUND_STAGES


class _TraceBase(NamedTuple):
    addr: np.ndarray       # (T, C, m) int32 line addresses
    is_write: np.ndarray   # (T, C, m) bool
    #: non-memory instructions amortized per request — a scalar, or a
    #: (C,) float32 vector for multi-app mixes (per-core intensity)
    insn_per_req: Union[float, np.ndarray]
    #: (C,) int32 app id per core (multi-tenant mixes), or None — the
    #: canonical single-app trace (all cores app 0)
    core_app: Optional[np.ndarray] = None
    #: (T, C, m) uint8 sectors each request asks for, for geometries
    #: with ``sectors_per_line > 1``; None = every request wants the
    #: whole line
    sect: Optional[np.ndarray] = None


class Trace(_TraceBase):
    """A request trace with strict dtype validation at the boundary.

    The simulator treats ``addr``/``is_write`` dtypes and the
    ``insn_per_req``/``core_app`` *shapes* as part of the executable
    key, so a hand-built trace that silently promoted ``addr`` to int64
    or ``is_write`` to int8 would either fail deep inside jit or double
    the compiled-executable count. Validation therefore happens here —
    at construction — not only inside ``make_trace``:

    * ``addr`` must already be int32 (use
      ``repro.core.trace.generators._require_int32`` to narrow safely);
    * ``is_write`` must be bool and shape-match ``addr``;
    * ``insn_per_req`` may be a python scalar or a (C,) vector; a
      uniform vector collapses to its scalar so single-app traces keep
      their executable regardless of how they were built;
    * ``core_app`` ids must be dense (every id in ``0..n_apps-1``
      assigned to at least one core); a single-app assignment collapses
      to ``None``, the canonical solo form;
    * ``sect``, where given, must be non-zero integer masks below 256
      in ``addr``'s shape (stored as uint8); the geometry it runs on
      bounds them further (:func:`check_sectors`).
    """
    __slots__ = ()

    def __new__(cls, addr, is_write, insn_per_req, core_app=None,
                sect=None):
        addr = np.asarray(addr)
        if addr.dtype != np.int32:
            raise ValueError(
                f"Trace.addr must be int32, got {addr.dtype}; narrow "
                "explicitly (repro.core.trace.generators._require_int32 "
                "checks for overflow)")
        if addr.ndim != 3:
            raise ValueError(
                f"Trace.addr must be (rounds, cores, m), got {addr.shape}")
        is_write = np.asarray(is_write)
        if is_write.dtype != np.bool_:
            raise ValueError(
                f"Trace.is_write must be bool, got {is_write.dtype}")
        if is_write.shape != addr.shape:
            raise ValueError(
                f"Trace.is_write shape {is_write.shape} != addr shape "
                f"{addr.shape}")
        C = addr.shape[1]
        if np.ndim(insn_per_req) == 0:
            insn_per_req = float(insn_per_req)
        else:
            v = np.asarray(insn_per_req, np.float32)
            if v.shape != (C,):
                raise ValueError(
                    f"Trace.insn_per_req must be a scalar or ({C},) "
                    f"per-core vector, got shape {v.shape}")
            if np.all(v == v[0]):
                insn_per_req = float(v[0])   # canonical scalar form
            else:
                insn_per_req = v
        if core_app is not None:
            ca = np.asarray(core_app)
            if not np.issubdtype(ca.dtype, np.integer):
                raise ValueError(
                    f"Trace.core_app must be integer app ids, got "
                    f"{ca.dtype}")
            if ca.shape != (C,):
                raise ValueError(
                    f"Trace.core_app must be ({C},) — one app id per "
                    f"core — got shape {ca.shape}")
            ids = np.unique(ca)
            if ids[0] != 0 or ids[-1] != ids.size - 1:
                raise ValueError(
                    "Trace.core_app ids must be dense 0..n_apps-1 "
                    f"(every app owns at least one core), got {ids.tolist()}")
            core_app = None if ids.size == 1 else ca.astype(np.int32)
        if sect is not None:
            sect = np.asarray(sect)
            if not np.issubdtype(sect.dtype, np.integer):
                raise ValueError(
                    f"Trace.sect must be integer masks, got {sect.dtype}")
            if sect.shape != addr.shape:
                raise ValueError(
                    f"Trace.sect shape {sect.shape} != addr shape "
                    f"{addr.shape}")
            if sect.size and (sect.min() < 1 or sect.max() > 255):
                raise ValueError(
                    "Trace.sect masks must lie in 1..255, got "
                    f"[{sect.min()}, {sect.max()}]")
            sect = sect.astype(np.uint8)
        return super().__new__(cls, addr, is_write, insn_per_req, core_app,
                               sect)

    def _replace(self, **kwds) -> "Trace":
        """Route through ``__new__`` so replaced traces re-validate.

        The inherited ``NamedTuple._replace`` builds via
        ``tuple.__new__`` and would silently skip the strict boundary
        checks (an int64 ``addr`` smuggled in this way would later be
        wrapped by ``jnp.asarray(..., int32)`` — exactly the corruption
        the validation exists to prevent).
        """
        fields = self._asdict()
        fields.update(kwds)
        return Trace(**fields)

    @property
    def n_cores(self) -> int:
        return self.addr.shape[1]

    @property
    def n_apps(self) -> int:
        """Number of co-scheduled apps (1 for the canonical solo form)."""
        return 1 if self.core_app is None else int(self.core_app.max()) + 1

    @property
    def core_app_ids(self) -> np.ndarray:
        """(C,) int32 app id per core; zeros for the solo form."""
        if self.core_app is None:
            return np.zeros((self.n_cores,), np.int32)
        return self.core_app

    @property
    def insn_vector(self) -> np.ndarray:
        """(C,) float64 per-core instruction intensity."""
        if np.ndim(self.insn_per_req) == 0:
            return np.full((self.n_cores,), float(self.insn_per_req))
        return np.asarray(self.insn_per_req, np.float64)


class AppStats(NamedTuple):
    """Per-app attribution slice of one simulation (raw counters).

    Raw sums only — never NaN — so nested tuple equality between the
    grid and per-point paths stays exact; ratios are derived
    properties (``l1_latency`` is NaN when no load of this app was ever
    fully served inside the L1 complex, mirroring ``SimResult``).
    """
    app: int            # dense app id (mix slot)
    cores: int          # cores assigned to this app
    instructions: float
    cycles: float       # completion time: max over the app's cores
    requests: float
    local_hits: float
    remote_hits: float
    l1_lat_sum: float
    l1_lat_n: float

    @property
    def ipc(self) -> float:
        return self.instructions / self.cycles

    @property
    def local_hit_rate(self) -> float:
        return self.local_hits / self.requests

    @property
    def remote_hit_rate(self) -> float:
        return self.remote_hits / self.requests

    @property
    def l1_hit_rate(self) -> float:
        return (self.local_hits + self.remote_hits) / self.requests

    @property
    def l1_latency(self) -> float:
        return self.l1_lat_sum / self.l1_lat_n if self.l1_lat_n \
            else float("nan")


class NocStats(NamedTuple):
    """Interconnect block of one simulation (``repro.core.noc``).

    Conservation counters are at injection granularity —
    ``flits_injected == flits_delivered + flits_queued`` holds after
    every round and at end-of-sim for every registered model (tier-1
    tested), up to float32 accumulation error when the per-port drain
    rate is not exactly representable (e.g. ``noc_bw/cluster_size =
    0.2``): backpressure may *defer* flits, never lose them.
    Utilizations normalize per-link busy cycles by the run's
    completion time; ``max_link_util`` is the hotspot link. The flit
    counters track traffic under every model (``ideal`` delivers
    everything instantly: ``injected == delivered``, ``queued == 0``);
    the *queueing and utilization* fields are 0.0 under ``ideal`` (no
    links, no delay), so solo and grid-stacked runs agree exactly
    regardless of how large a stacked sibling sized the carried link
    arrays.
    """
    flits_injected: float
    flits_delivered: float
    flits_queued: float        # still in a port queue at end-of-sim
    mean_queue_delay: float    # mean NoC delay over crossing requests
    max_link_util: float       # hotspot: busiest link busy / cycles
    mean_link_util: float      # mean busy / cycles over *active* links

    @property
    def conserved(self) -> bool:
        drift = abs(self.flits_injected
                    - (self.flits_delivered + self.flits_queued))
        return drift <= max(1e-6 * self.flits_injected, 1e-3)


_ZERO_NOC = NocStats(0.0, 0.0, 0.0, 0.0, 0.0, 0.0)


class SimResult(NamedTuple):
    ipc: float
    l1_latency: float          # mean per-load L1-complex completion time
    local_hit_rate: float
    remote_hit_rate: float     # served by a peer L1 (0 for private/decoupled)
    l1_hit_rate: float         # served anywhere in the L1 complex
    l2_accesses: float
    dram_accesses: float
    noc_flits: float
    cycles: float
    instructions: float
    #: per-app attribution (one AppStats per mix slot; a single entry
    #: covering every core for solo traces)
    per_app: Tuple[AppStats, ...] = ()
    #: interconnect metrics (all-zero under the default ``ideal`` model)
    noc: NocStats = _ZERO_NOC
    #: sectored geometries only (0 where ``sectors_per_line == 1``):
    #: L1 tag hits in the requester's own array lacking a requested
    #: sector, L2 tag hits lacking a needed sector, sectors from DRAM
    l1_sector_misses: float = 0.0
    l2_sector_misses: float = 0.0
    dram_sectors: float = 0.0


def _l1_state(geom, policies: Sequence[ArchPolicy]) -> tagarray.TagState:
    """L1 tag state sized for a whole dataflow group.

    The zoo state extensions (victim buffer, thrash counters) take the
    *maximum* the group's policies declare, so stacked family members
    share one state pytree; policies that ignore an extension are
    bit-exact whether it is zero-sized or not.
    """
    victim = max(p.victim_ways for p in policies)
    thrash = geom.n_cores if any(p.track_thrash for p in policies) else 0
    return tagarray.init_tag_state(geom.n_cores, geom.l1_sets,
                                   geom.l1_ways, victim_ways=victim,
                                   thrash_lanes=thrash,
                                   sectors=geom.sectors_per_line)


def _l2_state(geom) -> tagarray.TagState:
    return tagarray.init_tag_state(geom.l2_parts, geom.l2_sets, geom.l2_ways,
                                   sectors=geom.sectors_per_line)


def _noc_state(geom, models: Sequence[NocModel]):
    """Carried NoC state sized for a whole stacked model group.

    Mirrors :func:`_l1_state`: the link/queue arrays take the *maximum*
    ``n_links`` the group's models declare, so stacked members share
    one state pytree; a model that ignores the arrays (``ideal``) is
    bit-exact whether they are zero-sized or not.
    """
    return init_noc_state(max(m.n_links(geom) for m in models))


def _request_batch(geom, addr, is_write, sect=None) -> RequestBatch:
    """Flatten one round's (C, m) requests and derive routing indices."""
    C, m = addr.shape
    R = C * m
    addr = addr.reshape(R)
    is_write = is_write.reshape(R)
    if sect is not None:
        sect = sect.reshape(R).astype(jnp.int32)
    core = jnp.repeat(jnp.arange(C, dtype=jnp.int32), m)
    cluster = core // geom.cluster_size
    self_slot = core % geom.cluster_size
    set_idx = (addr % geom.l1_sets).astype(jnp.int32)
    bank = set_idx % geom.l1_banks
    peers = (cluster[:, None] * geom.cluster_size
             + jnp.arange(geom.cluster_size, dtype=jnp.int32)[None, :])
    return RequestBatch(addr=addr, is_write=is_write, core=core,
                        cluster=cluster, self_slot=self_slot,
                        set_idx=set_idx, bank=bank, peers=peers, sect=sect)


def _round(policy: ArchPolicy, nocs: Sequence[NocModel], noc_idx,
           geom, insn_per_req, core_app, state, xs, *,
           probe_backend: str = "lax",
           telemetry: Optional[TelemetryConfig] = None):
    """One simulation round. state=(l1, l2, noc, t, stats);
    xs=(addr, is_write), plus the (C, m) sector masks on a sectored
    geometry.

    ``geom`` is a :class:`TracedGeometry` view (or a concrete
    ``GpuGeometry``): structure fields are static, timing scalars may be
    tracers. ``insn_per_req`` is a scalar or (C,) vector; ``core_app``
    is the (C,) int32 app-id channel feeding the per-app attribution
    scatter-adds (all zeros for solo traces). ``nocs`` is the stacked
    interconnect-model group compiled into this executable; the traced
    ``noc_idx`` selects the active one (``lax.switch`` when the group
    has more than one member). ``probe_backend`` selects the L1 probe
    lowering (``repro.core.probe``) — *static*, since the backends
    lower structurally different programs; every backend is bit-exact.

    Each stage runs under its :data:`ROUND_STAGES` named scope; a scope
    changes only op metadata, never the computation.

    On a sectored geometry (``S = sectors_per_line > 1``) a hit needs
    every requested sector: the L1 policy reports each request's
    ``need`` (its sectors the own L1 lacks), the L2 serves it where its
    way holds them all and fetches the rest from DRAM, and each
    transfer of ``k`` sectors costs ``k * flits_per_line / S`` flits.
    """
    l1, l2, noc, t, stats = state
    addr, is_write, *sect = xs               # (C, m)
    C, m = addr.shape
    reqs = _request_batch(geom, addr, is_write, *sect)
    addr = reqs.addr                         # (R,) flattened
    R = reqs.n_requests
    sectored = reqs.sect is not None
    if sectored:
        sector_flits = geom.flits_per_line / geom.sectors_per_line

    # ---- L1 policy stage (the only architecture-specific part) ------------
    with jax.named_scope(_L1):
        out = policy.l1_stage(geom, l1, reqs, t, backend=probe_backend)
        l1 = out.l1
        go_l2 = out.go_l2
        noc_flits = jnp.asarray(out.noc_flits, jnp.float32)
        occupancy = out.occupancy

    # ---- L2 stage ---------------------------------------------------------
    with jax.named_scope(_L2):
        l2_part = (addr % geom.l2_parts).astype(jnp.int32)
        l2_set = ((addr // geom.l2_parts) % geom.l2_sets).astype(jnp.int32)
        l2_hit, l2_way, _ = tagarray.probe(l2, l2_part, l2_set, addr)
        if sectored:
            with jax.named_scope(tagarray.SECTOR_SCOPE):
                l2_tag_hit = l2_hit
                fetch = jnp.where(
                    l2_tag_hit,
                    out.need & ~tagarray.sectors_at(l2, l2_part, l2_set,
                                                    l2_way),
                    out.need)
                l2_hit = fetch == 0
        l2_rank, l2_size = group_rank(l2_part, go_l2, geom.l2_parts)
        l2_time = (geom.lat_l2 + l2_rank.astype(jnp.float32) * geom.svc_l2
                   + jnp.where(l2_hit, 0.0, geom.lat_dram * 1.0))
        occupancy = jnp.maximum(
            occupancy,
            jnp.where(go_l2, l2_size.astype(jnp.float32) * geom.svc_l2, 0.0))
        if sectored:
            l2 = tagarray.touch(l2, l2_part, l2_set, l2_way, t,
                                go_l2 & l2_tag_hit, sect=fetch)
            l2, _ = tagarray.fill(l2, l2_part, l2_set, l2_way, addr, t,
                                  go_l2 & ~l2_tag_hit, sect=out.need)
            noc_flits = noc_flits + jnp.sum(jnp.where(
                go_l2, jax.lax.population_count(out.need), 0)
            ) * sector_flits
        else:
            l2 = tagarray.touch(l2, l2_part, l2_set, l2_way, t,
                                go_l2 & l2_hit)
            l2, _ = tagarray.fill(l2, l2_part, l2_set, l2_way, addr, t,
                                  go_l2 & ~l2_hit)
            noc_flits = noc_flits + jnp.sum(go_l2) * geom.flits_per_line

    # ---- L1 fill on L2 return (and on remote fetch: replicate locally) ----
    with jax.named_scope(_FILL):
        fill_mask = go_l2 | out.remote_hits
        if out.bypass_fill is not None:
            fill_mask = fill_mask & ~out.bypass_fill
        _, fway, _ = tagarray.probe(l1, out.fill_cache, out.fill_set, addr,
                                    policy=policy.replacement)
        if policy.fills_own_core:
            if out.fill_cache is not reqs.core:
                raise ValueError(
                    f"{policy.name!r} declares fills_own_core but its "
                    "fill_cache is not reqs.core")
            l1, wb = tagarray.fill_rows(l1, out.fill_set, fway, addr, t,
                                        fill_mask, dirty=reqs.is_write,
                                        sect=out.need)
        else:
            l1, wb = tagarray.fill(l1, out.fill_cache, out.fill_set, fway,
                                   addr, t, fill_mask, dirty=reqs.is_write)
        if sectored:
            # write-backs carry the evicted line's valid sectors
            noc_flits = noc_flits + jnp.sum(
                jax.lax.population_count(wb)) * sector_flits
        else:
            noc_flits = noc_flits + jnp.sum(wb) * geom.flits_per_line

    # ---- NoC stage: remote-probe/remote-data flits through the active
    # interconnect model (repro.core.noc). The policies' own memoryless
    # per-round contention stays put; the model adds topology effects —
    # cross-round queue backpressure, hop latency, link hotspots — and
    # the `ideal` model adds exactly zero (bit-exact with the pre-NoC
    # simulator).
    with jax.named_scope(_NOC):
        req_flits = out.noc_req_flits
        if req_flits is None:
            req_flits = out.remote_hits * (geom.flits_per_line * 1.0)
        req_flits = jnp.asarray(req_flits, jnp.float32)
        traffic = NocTraffic(
            src=out.noc_src if out.noc_src is not None else reqs.core,
            dst=reqs.core, cluster=reqs.cluster, flits=req_flits,
            mask=req_flits > 0)
        if len(nocs) == 1:
            transit = nocs[0].transit(geom, noc, traffic)
        else:
            transit = jax.lax.switch(
                noc_idx, [functools.partial(m.transit, geom) for m in nocs],
                noc, traffic)
        noc = transit.state
        occupancy = jnp.maximum(occupancy, transit.occupancy)

    # ---- timing ------------------------------------------------------------
    with jax.named_scope(_TIMING):
        latency = (jnp.where(out.served, out.l1_time, out.pre_l2 + l2_time)
                   + transit.delay)                                     # (R,)
        # Warp multithreading hides individual request latencies; the core's
        # sustained pace is set by *mean* outstanding latency per load, while
        # serial-resource occupancy is a hard throughput bound (max over m).
        per_core_lat = latency.reshape(C, m).mean(axis=1)
        per_core_occ = occupancy.reshape(C, m).max(axis=1)
        pace = m * insn_per_req / geom.issue_rate
        round_cost = jnp.maximum(jnp.maximum(pace, per_core_occ),
                                 per_core_lat / geom.hide)         # (C,)

        # Fig.10 metric: completion time of the L1 accesses of one load
        # instruction, over loads fully served by the L1 complex. The NoC
        # transit delay of a remote hit is part of that completion time
        # (exactly 0.0 under `ideal`, so the golden pins are unaffected).
        all_served = out.served.reshape(C, m).all(axis=1)
        l1_complete = (out.l1_time + transit.delay).reshape(C, m).max(axis=1)

        # Per-app attribution: hit counters scatter-add by the issuing
        # core's app id inside the existing carry (hit counts are small
        # integers in float32 — exact regardless of accumulation order).
        req_app = core_app[reqs.core]                               # (R,)
        f32 = jnp.float32
        app_served_lat = jnp.where(all_served, l1_complete, 0.0)    # (C,)

        stats = {
            "cycles": stats["cycles"] + round_cost,
            "l1_lat_sum": stats["l1_lat_sum"] + jnp.sum(app_served_lat),
            "l1_lat_n": stats["l1_lat_n"] + jnp.sum(all_served),
            "local_hits": stats["local_hits"] + jnp.sum(out.local_hits),
            "remote_hits": stats["remote_hits"] + jnp.sum(out.remote_hits),
            "requests": stats["requests"] + R,
            "l2_accesses": stats["l2_accesses"] + jnp.sum(go_l2),
            "dram": stats["dram"] + jnp.sum(go_l2 & ~l2_hit),
            "noc_flits": stats["noc_flits"] + noc_flits,
            "app_local": stats["app_local"]
            .at[req_app].add(out.local_hits.astype(f32)),
            "app_remote": stats["app_remote"]
            .at[req_app].add(out.remote_hits.astype(f32)),
            "app_lat_sum": stats["app_lat_sum"]
            .at[core_app].add(app_served_lat),
            "app_lat_n": stats["app_lat_n"]
            .at[core_app].add(all_served.astype(f32)),
        }
        if sectored:
            prev = state[4]
            stats["l1_sector_misses"] = (prev["l1_sector_misses"]
                                         + jnp.sum(out.sector_misses))
            stats["l2_sector_misses"] = (prev["l2_sector_misses"]
                                         + jnp.sum(go_l2 & l2_tag_hit
                                                   & ~l2_hit))
            stats["dram_sectors"] = prev["dram_sectors"] + jnp.sum(
                jnp.where(go_l2, jax.lax.population_count(fetch), 0))
        if telemetry is not None and telemetry.histograms:
            # log2-bucketed L1-complete latency histogram over served
            # loads (unserved cores contribute an add of 0 — a no-op).
            bucket = log2_bucket(l1_complete, telemetry.sim_hist_bins)
            stats["lat_hist"] = state[4]["lat_hist"] \
                .at[bucket].add(all_served.astype(jnp.int32))
    return (l1, l2, noc, t + 1, stats), None


def _init_stats(geom, n_apps: int = 1,
                telemetry: Optional[TelemetryConfig] = None
                ) -> Dict[str, jnp.ndarray]:
    z = jnp.float32(0.0)
    app = jnp.zeros((n_apps,), jnp.float32)
    stats = {"cycles": jnp.zeros((geom.n_cores,), jnp.float32),
             "l1_lat_sum": z, "l1_lat_n": z, "local_hits": z,
             "remote_hits": z, "requests": z, "l2_accesses": z,
             "dram": z, "noc_flits": z,
             "app_local": app, "app_remote": app,
             "app_lat_sum": app, "app_lat_n": app}
    if geom.sectors_per_line > 1:
        stats.update(l1_sector_misses=z, l2_sector_misses=z, dram_sectors=z)
    if telemetry is not None and telemetry.histograms:
        stats["lat_hist"] = jnp.zeros((telemetry.sim_hist_bins,),
                                      jnp.int32)
    return stats


def _sim_core(archs: Tuple[str, ...], nocs: Tuple[str, ...], point_arrays,
              structure: GeomStructure, n_apps: int = 1,
              probe_backend: str = "lax",
              telemetry: Optional[TelemetryConfig] = None):
    """Scan one grid point through the round pipeline.

    ``archs`` is a *dataflow group*: one or more same-dataflow
    architectures compiled together, the active one selected per point
    by the traced ``policy_idx`` (``lax.switch`` over the round step);
    ``nocs`` is the stacked interconnect-model group, selected by the
    traced ``noc_idx`` the same way (an inner switch over the NoC
    stage). ``point_arrays = (addr, is_write, insn_per_req, core_app,
    scalars, policy_idx, noc_idx)``, with the (T, C, m) sector masks
    last on a sectored geometry — everything but ``archs``/
    ``nocs``/``structure``/``n_apps``/``probe_backend`` is traced, so
    one executable serves whole (policy, NoC, timing-geometry, trace)
    grids; ``n_apps`` sizes the per-app attribution accumulators
    (static — mixes with the same app count share executables).
    ``probe_backend`` is static too: unlike NoC models, probe backends
    lower structurally different round programs (XLA chain vs Pallas
    kernel), so each gets its own executable rather than a traced
    switch branch.

    ``telemetry`` (static, default ``None``) turns on windowed
    observability: the scan is restructured into an outer scan over
    ``rounds/window`` windows of an inner ``window``-round scan, and
    each outer step emits a *cumulative* snapshot of the stats + NoC
    carry (key ``"timeline"``, leading window axis). The per-round op
    sequence is identical to the flat scan, so final counters — and
    every ``SimResult`` derived from them — are bit-equal with and
    without telemetry; ``None`` never traces any of this, keeping the
    default executables byte-identical.
    """
    addr, is_write, insn_per_req, core_app, scalars, policy_idx, \
        noc_idx, *sect = point_arrays
    xs = (addr, is_write, *sect)
    geom = TracedGeometry(structure, scalars)
    policies = [get_arch(a) for a in archs]
    noc_models = [get_noc(n) for n in nocs]
    state = (_l1_state(geom, policies), _l2_state(geom),
             _noc_state(geom, noc_models), jnp.int32(0),
             _init_stats(geom, n_apps, telemetry))
    steps = [functools.partial(_round, p, noc_models, noc_idx, geom,
                               insn_per_req, core_app,
                               probe_backend=probe_backend,
                               telemetry=telemetry)
             for p in policies]
    if len(steps) == 1:
        step = steps[0]
    else:
        def step(carry, xs):
            return jax.lax.switch(policy_idx, steps, carry, xs)
    if telemetry is None:
        (l1, l2, noc, t, stats), _ = jax.lax.scan(step, state, xs)
        return {**stats, "noc": noc}

    T = addr.shape[0]
    W = telemetry.window_for(T)
    xs = tuple(x.reshape((T // W, W) + x.shape[1:]) for x in xs)

    def window_step(carry, xs_w):
        carry, _ = jax.lax.scan(step, carry, xs_w)
        _, _, noc_w, _, stats_w = carry
        return carry, {"stats": stats_w, "noc": noc_w}

    (l1, l2, noc, t, stats), snaps = jax.lax.scan(window_step, state, xs)
    return {**stats, "noc": noc, "timeline": snaps}


#: One compilation per (arch group, NoC group, trace shape, geometry
#: structure, app count, probe backend, telemetry config — ``None``
#: keys the exact pre-telemetry executables).
_simulate = jax.jit(_sim_core, static_argnums=(0, 1, 3, 4, 5, 6))

#: Batched form: vmap over a leading grid-point axis, still one
#: compilation. ``repro.core.sweep`` adds device sharding on top.
_simulate_batch = jax.jit(
    lambda archs, nocs, point_arrays, structure, n_apps, probe_backend: \
    jax.vmap(
        lambda pa: _sim_core(archs, nocs, pa, structure, n_apps,
                             probe_backend))(point_arrays),
    static_argnums=(0, 1, 3, 4, 5))


def sector_masks(trace: Trace, sectors: int) -> Tuple[np.ndarray, ...]:
    """The trace's sector masks as a sectored geometry's scan takes them
    (whole-line masks where the trace has none); ``()`` for ``sectors
    == 1``, whose programs take no mask."""
    if sectors == 1:
        return ()
    if trace.sect is None:
        return (np.full(trace.addr.shape, (1 << sectors) - 1, np.uint8),)
    return (trace.sect,)


def _trace_arrays(trace: Trace, sectors: int = 1):
    """One trace's traced leaves: (addr, is_write, insn, core_app), and
    its sector masks on a sectored geometry."""
    addr = jnp.asarray(trace.addr, jnp.int32)
    is_write = jnp.asarray(trace.is_write, bool)
    if np.ndim(trace.insn_per_req) == 0:
        insn = jnp.float32(trace.insn_per_req)
    else:
        insn = jnp.asarray(trace.insn_per_req, jnp.float32)
    core_app = jnp.asarray(trace.core_app_ids, jnp.int32)
    sect = tuple(jnp.asarray(x, jnp.uint8)
                 for x in sector_masks(trace, sectors))
    return (addr, is_write, insn, core_app) + sect


def _point_arrays(trace_like, scalars, policy_idx=0, noc_idx=0):
    """Pack one grid point's traced leaves for :func:`_sim_core`."""
    addr, is_write, insn, core_app, *sect = trace_like
    return (addr, is_write, insn, core_app, scalars,
            jnp.int32(policy_idx), jnp.int32(noc_idx), *sect)


def round_signature(group: Tuple[str, ...], arch: str,
                    structure: GeomStructure,
                    round_shape: Tuple[int, int],
                    insn_shape: Tuple[int, ...] = (),
                    n_apps: int = 1,
                    noc_group: Tuple[str, ...] = ("ideal",),
                    noc: str = "ideal",
                    probe_backend: str = "lax"):
    """Abstract shape/dtype pytree of one scanned round of ``arch``.

    The round is evaluated (``jax.eval_shape`` — no compilation, no
    FLOPs) with the L1 state sized for the whole dataflow ``group``
    and the NoC state sized for the whole ``noc_group``, exactly as
    :func:`_sim_core` would compile them. Policies (and NoC models)
    that may stack into one executable must produce identical
    signatures — the carried state pytrees are what ``lax.switch``
    requires to line up — and ``repro.core.sweep.SweepGrid`` validates
    that with this function before it buckets a grid.
    ``insn_shape``/``n_apps`` mirror the trace's instruction-intensity
    shape and app count: mixes carry per-app accumulators in the same
    pytree. ``probe_backend`` selects the probe lowering — every
    backend must (and does) carry an identical state pytree, which this
    signature also certifies (the Pallas path abstract-evaluates here
    without running the kernel body).
    """
    C, m = round_shape
    policies = [get_arch(a) for a in group]
    noc_models = [get_noc(n) for n in noc_group]
    scalars = GeomScalars(*(jax.ShapeDtypeStruct((), jnp.float32)
                            for _ in GEOM_SCALAR_FIELDS))
    sect = ((jax.ShapeDtypeStruct((C, m), jnp.uint8),)
            if structure.sectors_per_line > 1 else ())

    def one_round(scalars, addr, is_write, insn, core_app, *sect):
        geom = TracedGeometry(structure, scalars)
        state = (_l1_state(geom, policies), _l2_state(geom),
                 _noc_state(geom, noc_models), jnp.int32(0),
                 _init_stats(geom, n_apps))
        # evaluate the *selected* (arch, noc) member's round over state
        # sized for the full groups — members whose dataflow diverges
        # from the group produce a different signature here instead of
        # an opaque lax.switch failure inside the compiled executable
        new_state, _ = _round(get_arch(arch), [get_noc(noc)], jnp.int32(0),
                              geom, insn, core_app,
                              state, (addr, is_write, *sect),
                              probe_backend=probe_backend)
        return new_state

    out = jax.eval_shape(one_round, scalars,
                         jax.ShapeDtypeStruct((C, m), jnp.int32),
                         jax.ShapeDtypeStruct((C, m), jnp.bool_),
                         jax.ShapeDtypeStruct(insn_shape, jnp.float32),
                         jax.ShapeDtypeStruct((C,), jnp.int32), *sect)
    leaves, treedef = jax.tree.flatten(out)
    return treedef, tuple((l.shape, str(l.dtype)) for l in leaves)


def _summarize(stats, trace: Trace) -> SimResult:
    T, C, m = trace.addr.shape
    cycles_per_core = np.asarray(stats["cycles"], np.float64)  # (C,)
    if np.ndim(trace.insn_per_req) == 0:
        # unchanged scalar float path: pre-mix results stay bit-exact
        instructions = T * C * m * float(trace.insn_per_req)
    else:
        instructions = float(T * m * np.sum(trace.insn_vector))
    cycles = float(stats["cycles"].max())
    requests = float(stats["requests"])
    local = float(stats["local_hits"])
    remote = float(stats["remote_hits"])
    lat_n = float(stats["l1_lat_n"])

    ns = stats["noc"]
    busy = np.asarray(ns["link_busy"], np.float64)
    active = int((busy > 0).sum())
    delay_n = float(ns["delay_n"])
    noc_block = NocStats(
        flits_injected=float(ns["injected"]),
        flits_delivered=float(ns["delivered"]),
        flits_queued=float(np.asarray(ns["queue"], np.float64).sum()),
        mean_queue_delay=(float(ns["delay_sum"]) / delay_n if delay_n
                          else 0.0),
        max_link_util=(float(busy.max()) / cycles if busy.size else 0.0),
        mean_link_util=(float(busy.sum()) / (cycles * active) if active
                        else 0.0),
    )

    ids = trace.core_app_ids
    insn_vec = trace.insn_vector
    per_app = []
    for a in range(trace.n_apps):
        sel = ids == a
        k = int(sel.sum())
        per_app.append(AppStats(
            app=a, cores=k,
            instructions=float(T * m * insn_vec[sel].sum()),
            cycles=float(cycles_per_core[sel].max()),
            requests=float(T * k * m),
            local_hits=float(stats["app_local"][a]),
            remote_hits=float(stats["app_remote"][a]),
            l1_lat_sum=float(stats["app_lat_sum"][a]),
            l1_lat_n=float(stats["app_lat_n"][a])))

    sector = {k: float(stats[k]) for k in
              ("l1_sector_misses", "l2_sector_misses", "dram_sectors")
              if k in stats}
    return SimResult(
        ipc=instructions / cycles,
        # NaN when no load was ever fully served inside the L1 complex
        # (possible on very short or all-streaming traces)
        l1_latency=(float(stats["l1_lat_sum"]) / lat_n if lat_n
                    else float("nan")),
        local_hit_rate=local / requests,
        remote_hit_rate=remote / requests,
        l1_hit_rate=(local + remote) / requests,
        l2_accesses=float(stats["l2_accesses"]),
        dram_accesses=float(stats["dram"]),
        noc_flits=float(stats["noc_flits"]),
        cycles=cycles,
        instructions=instructions,
        per_app=tuple(per_app),
        noc=noc_block,
        **sector,
    )


def _check_arch(arch: str) -> None:
    if arch not in registered_archs():
        raise ValueError(f"arch must be one of {registered_archs()}")


def _check_noc(noc: str) -> None:
    if noc not in registered_nocs():
        raise ValueError(f"noc must be one of {registered_nocs()}")


#: Probe backends that lower the sector compares (``repro.core.probe``).
SECTOR_PROBE_BACKENDS = ("lax", "lax_unfused")


def check_sectors(arch: str, sectors: int, trace: Trace,
                  probe_backend: str = "lax") -> None:
    """Refuse a point the sectored model does not cover.

    ``sectors_per_line`` must lie in 1..8. A sector mask needs a
    sectored geometry, and its masks must name sectors of the line.
    Sectored geometries run the policies that model sectors
    (``ArchPolicy.sectored``, under LRU replacement) on a backend
    that lowers the sector compares.
    """
    if not 1 <= sectors <= 8:
        raise ValueError(
            f"sectors_per_line must lie in 1..8, got {sectors}")
    if trace.sect is not None:
        if sectors == 1:
            raise ValueError(
                "the trace carries sector masks but the geometry has "
                "whole lines (sectors_per_line = 1)")
        if trace.sect.size and int(trace.sect.max()) >= 1 << sectors:
            raise ValueError(
                f"sector masks up to {int(trace.sect.max())} name "
                f"sectors beyond the line's {sectors}")
    if sectors == 1:
        return
    policy = get_arch(arch)
    if not (policy.sectored
            and policy.replacement is tagarray.ReplacementPolicy.LRU):
        raise ValueError(
            f"architecture {arch!r} does not model sectored lines "
            f"(sectors_per_line = {sectors}); use private or ata")
    if probe_backend not in SECTOR_PROBE_BACKENDS:
        raise ValueError(
            f"probe_backend {probe_backend!r} does not lower sectored "
            f"lines; use one of {SECTOR_PROBE_BACKENDS}")


def trace_kind(trace: Trace) -> tuple:
    """The executable-keying shape of a trace: (addr shape, insn shape,
    n_apps). Traces sharing a kind (and a dataflow group + geometry
    structure) share one compiled executable."""
    return (trace.addr.shape, np.shape(trace.insn_per_req), trace.n_apps)


def simulate(arch: str, trace: Trace,
             geom: GpuGeometry = PAPER_GEOMETRY, *,
             noc: str = "ideal",
             probe_backend: str = "lax",
             telemetry: Optional[TelemetryConfig] = None):
    """Run a trace through one architecture and summarize.

    ``noc`` selects the interconnect model (``repro.core.noc``); the
    default ``ideal`` reproduces the pre-NoC simulator bit-exactly.
    ``probe_backend`` selects the L1 probe lowering
    (``repro.core.probe``); every backend returns bit-identical
    results — the axis trades compile target (XLA vs Pallas/Mosaic)
    and speed, never semantics.

    ``telemetry`` (a :class:`~repro.core.telemetry.TelemetryConfig`)
    turns on windowed observability: the return becomes a
    ``(SimResult, repro.obs.SimTimeline)`` pair, with the
    :class:`SimResult` bit-equal to the ``telemetry=None`` run (the
    window restructuring preserves the per-round op sequence). The
    default ``None`` compiles and reuses exactly the pre-telemetry
    executable.
    """
    _check_arch(arch)
    _check_noc(noc)
    _check_probe_backend(probe_backend)
    check_sectors(arch, geom.sectors_per_line, trace, probe_backend)
    if telemetry is not None:
        telemetry.window_for(trace.addr.shape[0])
    structure, scalars = split_geometry(geom)
    stats = jax.device_get(_simulate(
        (arch,), (noc,),
        _point_arrays(_trace_arrays(trace, geom.sectors_per_line), scalars),
        structure, trace.n_apps, probe_backend, telemetry))
    if telemetry is None:
        return _summarize(stats, trace)
    from repro.obs.timeline import SimTimeline   # local: obs sits above core
    snaps = stats.pop("timeline")
    result = _summarize(stats, trace)
    tl = SimTimeline.from_snapshots(
        snaps, telemetry, rounds=trace.addr.shape[0],
        meta={"arch": arch, "noc": noc, "n_apps": trace.n_apps,
              "n_cores": trace.n_cores})
    return result, tl


def simulate_batch(arch: str, traces: Sequence[Trace],
                   geom: GpuGeometry = PAPER_GEOMETRY, *,
                   noc: str = "ideal",
                   probe_backend: str = "lax") -> List[SimResult]:
    """Run many same-shape traces through one architecture in one call.

    The traces are stacked on a new leading axis and the scanned
    simulation is ``jax.vmap``-ed over it, so the whole sweep is a single
    compiled executable (and a single device dispatch) regardless of how
    many traces are in the batch. All traces must share one
    :func:`trace_kind` — (T, C, m) shape, instruction-intensity shape,
    and app count; :func:`simulate_many` handles mixed kinds by
    grouping.
    """
    _check_arch(arch)
    _check_noc(noc)
    _check_probe_backend(probe_backend)
    if not traces:
        return []
    kinds = {trace_kind(t) for t in traces}
    if len(kinds) != 1:
        raise ValueError(
            f"simulate_batch needs same-shape, same-kind traces "
            f"((T, C, m), insn shape, n_apps), got {sorted(kinds)}; use "
            "simulate_many for mixed kinds")
    for t in traces:
        check_sectors(arch, geom.sectors_per_line, t, probe_backend)
    structure, scalars = split_geometry(geom)
    B = len(traces)
    n_apps = traces[0].n_apps
    addr = jnp.asarray(np.stack([t.addr for t in traces]), jnp.int32)
    is_write = jnp.asarray(np.stack([t.is_write for t in traces]), bool)
    if np.ndim(traces[0].insn_per_req) == 0:
        insn = jnp.asarray([t.insn_per_req for t in traces], jnp.float32)
    else:
        insn = jnp.asarray(np.stack([t.insn_per_req for t in traces]),
                           jnp.float32)
    core_app = jnp.asarray(np.stack([t.core_app_ids for t in traces]),
                           jnp.int32)
    sect = tuple(jnp.asarray(np.stack(x), jnp.uint8) for x in zip(
        *(sector_masks(t, geom.sectors_per_line) for t in traces)))
    batched = ((addr, is_write, insn, core_app,
                jax.tree.map(lambda s: jnp.broadcast_to(s, (B,)), scalars),
                jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32))
               + sect)
    stats = jax.device_get(_simulate_batch((arch,), (noc,), batched,
                                           structure, n_apps,
                                           probe_backend))
    return [_summarize(jax.tree.map(lambda a: a[b], stats), traces[b])
            for b in range(len(traces))]


def simulate_many(arch: str, traces: Sequence[Trace],
                  geom: GpuGeometry = PAPER_GEOMETRY, *,
                  noc: str = "ideal",
                  probe_backend: str = "lax") -> List[SimResult]:
    """``simulate_batch`` over arbitrary traces: group by kind, preserve
    input order."""
    _check_arch(arch)
    _check_noc(noc)
    _check_probe_backend(probe_backend)
    groups: Dict[tuple, List[int]] = {}
    for i, t in enumerate(traces):
        groups.setdefault(trace_kind(t), []).append(i)
    out: List[SimResult] = [None] * len(traces)  # type: ignore[list-item]
    for idxs in groups.values():
        for i, r in zip(idxs, simulate_batch(
                arch, [traces[i] for i in idxs], geom, noc=noc,
                probe_backend=probe_backend)):
            out[i] = r
    return out
