"""GPU geometry + timing model constants (paper Table II).

The simulated GPU matches the paper's GPGPU-sim v4.0 configuration:
30 SIMT cores in 3 clusters of 10, 64KB 64-way L1 per core (128B lines,
8 sets, 4 banks, 32-cycle latency), 24x128KB 16-way L2 partitions
(188-cycle latency), crossbar NoC.

Service times model *occupancy* (throughput contention); latencies model
the uncontended critical path. The `hide` divisor models warp-level
latency hiding (4 GTO schedulers / core, deep multithreading).

For geometry sweeps the fields split into two kinds:

* **structure** fields (core/cluster counts, set/way/bank/partition
  counts) determine array shapes and routing-index arithmetic — they
  must be static under ``jax.jit``, and geometries are grouped by them;
* **scalar** fields (latencies, service times, rates) only enter the
  timing arithmetic — they are traced, so geometries differing only in
  scalars share one compiled executable.

:func:`split_geometry` performs the split; :class:`TracedGeometry`
recombines a static :class:`GeomStructure` with (possibly traced)
:class:`GeomScalars` behind the same attribute names, so architecture
policies run unchanged over either a concrete ``GpuGeometry`` or a
traced view.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class GpuGeometry:
    # --- organization -----------------------------------------------------
    n_cores: int = 30
    cluster_size: int = 10
    # L1: 64KB / 128B lines = 512 lines, 64-way -> 8 sets, 4 banks
    l1_sets: int = 8
    l1_ways: int = 64
    l1_banks: int = 4
    # L2: 24 partitions x 128KB / 128B = 1024 lines, 16-way -> 64 sets
    l2_parts: int = 24
    l2_sets: int = 64
    l2_ways: int = 16
    # Sectors per line: tags per 128B line, data moved and tracked in
    # line/sectors_per_line pieces (Volta and later; GPGPU-Sim 4). 1 is
    # the paper's whole-line model.
    sectors_per_line: int = 1

    # --- uncontended latencies (cycles) ------------------------------------
    lat_l1: int = 32
    lat_xbar: int = 2        # ATA intra-cluster crossbar hop (data transfer)
    lat_home: int = 16       # decoupled-sharing core->home NoC round trip
    lat_l2: int = 188
    lat_dram: int = 320
    lat_probe: int = 24      # remote-sharing probe round-trip (uncontended)

    # --- service / occupancy times (cycles per request at the resource) ----
    svc_bank: int = 8        # decoupled-sharing home-cache bank port
    svc_port: int = 2        # ATA remote-data port
    svc_probe: int = 1       # remote-sharing tag-probe service per probe
    svc_l2: int = 4          # L2 partition port
    flits_per_line: int = 4  # 128B line / 40B flit (rounded up)
    noc_bw: float = 16.0     # flits/cycle the probe network sustains/cluster

    # --- interconnect topology (repro.core.noc models) ----------------------
    # Per-port forwarding rate is noc_bw / cluster_size (the cluster's
    # probe-network bandwidth shared across its cores' remote-data
    # ports); these scalars shape the topology-aware models only — the
    # `ideal` NoC ignores them, so the paper geometry is unchanged.
    noc_drain: float = 32.0  # cycles of NoC forwarding budget per round
    noc_queue: float = 128.0  # per-port injection-queue capacity (flits)
    ring_hop: float = 2.0    # cycles per ring hop between cluster slots

    # --- core pipeline model ------------------------------------------------
    issue_rate: float = 4.0  # peak insn/cycle/core (4 GTO schedulers)
    hide: float = 10.0       # warp-level latency-hiding divisor

    @property
    def n_clusters(self) -> int:
        return self.n_cores // self.cluster_size


#: Default geometry = paper Table II.
PAPER_GEOMETRY = GpuGeometry()


#: Fields that fix array shapes / routing arithmetic (static under jit).
GEOM_STRUCTURE_FIELDS = ("n_cores", "cluster_size", "l1_sets", "l1_ways",
                         "l1_banks", "l2_parts", "l2_sets", "l2_ways",
                         "sectors_per_line")

#: Timing fields that only enter arithmetic (traceable under jit).
GEOM_SCALAR_FIELDS = ("lat_l1", "lat_xbar", "lat_home", "lat_l2",
                      "lat_dram", "lat_probe", "svc_bank", "svc_port",
                      "svc_probe", "svc_l2", "flits_per_line", "noc_bw",
                      "noc_drain", "noc_queue", "ring_hop",
                      "issue_rate", "hide")


class GeomStructure(NamedTuple):
    """The shape-determining subset of :class:`GpuGeometry` (hashable, so
    it can be a static jit argument; sweeps group geometries by it)."""
    n_cores: int
    cluster_size: int
    l1_sets: int
    l1_ways: int
    l1_banks: int
    l2_parts: int
    l2_sets: int
    l2_ways: int
    sectors_per_line: int = 1

    @property
    def n_clusters(self) -> int:
        return self.n_cores // self.cluster_size


class GeomScalars(NamedTuple):
    """The timing subset of :class:`GpuGeometry` as float32 leaves — a
    pytree, so it can be traced, stacked on a sweep axis, and vmapped."""
    lat_l1: jnp.ndarray
    lat_xbar: jnp.ndarray
    lat_home: jnp.ndarray
    lat_l2: jnp.ndarray
    lat_dram: jnp.ndarray
    lat_probe: jnp.ndarray
    svc_bank: jnp.ndarray
    svc_port: jnp.ndarray
    svc_probe: jnp.ndarray
    svc_l2: jnp.ndarray
    flits_per_line: jnp.ndarray
    noc_bw: jnp.ndarray
    noc_drain: jnp.ndarray
    noc_queue: jnp.ndarray
    ring_hop: jnp.ndarray
    issue_rate: jnp.ndarray
    hide: jnp.ndarray


def geom_structure(geom: GpuGeometry) -> GeomStructure:
    """The shape-determining key of ``geom`` alone — no device commits,
    so grid validation can key geometries without paying
    :func:`split_geometry`'s scalar transfers."""
    return GeomStructure(*(getattr(geom, f) for f in GEOM_STRUCTURE_FIELDS))


def split_geometry(geom: GpuGeometry):
    """``geom`` -> (static :class:`GeomStructure`, f32 :class:`GeomScalars`)."""
    scalars = GeomScalars(
        *(jnp.float32(getattr(geom, f)) for f in GEOM_SCALAR_FIELDS))
    return geom_structure(geom), scalars


class TracedGeometry:
    """A ``GpuGeometry`` look-alike over (static structure, traced scalars).

    Architecture policies and the simulator stages read geometry fields
    by attribute; this view serves structure fields as Python ints (so
    shapes and ``group_rank`` key counts stay static) and timing fields
    as float32 values that may be jit tracers (so scalar geometry sweeps
    share one executable).
    """

    __slots__ = ("structure", "scalars")

    def __init__(self, structure: GeomStructure, scalars: GeomScalars):
        object.__setattr__(self, "structure", structure)
        object.__setattr__(self, "scalars", scalars)

    def __getattr__(self, name: str):
        if name in GEOM_STRUCTURE_FIELDS:
            return getattr(self.structure, name)
        if name in GEOM_SCALAR_FIELDS:
            return getattr(self.scalars, name)
        raise AttributeError(name)

    @property
    def n_clusters(self) -> int:
        return self.structure.n_clusters
