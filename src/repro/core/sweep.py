"""Device-sharded, multi-axis parameter-grid sweep engine.

:class:`SweepGrid` takes a cartesian grid over four axes —

    archs   : architecture-policy names (``repro.core.arch`` registry)
    geoms   : :class:`GpuGeometry` points
    traces  : :class:`Trace` points (e.g. all kernels of an app)
    nocs    : interconnect-model names (``repro.core.noc`` registry;
              defaults to the bit-exact ``ideal``)
    probe_backends : L1 probe lowerings (``repro.core.probe``;
              defaults to the fused ``lax`` path — backends return
              bit-identical results but compile separate executables)

— and runs every point through the round-pipeline simulator while
compiling as few executables as possible:

* **policy stacking** — architectures whose policies share a
  ``stack_key`` (identical round dataflow, e.g. ``ata``/``ata_fifo``/
  ``ata_bypass``) are compiled into *one* executable; the active policy
  is selected per grid point by a traced index (``lax.switch`` inside
  the scanned round). Note the tradeoff: under ``vmap`` a batched
  switch index lowers to *compute-all-branches-and-select*, so a
  stacked bucket pays roughly group-size x the per-round FLOPs in
  exchange for one compilation and one dispatch — a good trade while
  compile time dominates (small grids, wide families, CI smoke) but
  worth splitting into per-policy grids when a single stacked bucket
  grows runtime-bound.
* **geometry batching** — timing scalars (latencies, service times,
  rates) are traced (:class:`repro.core.geometry.GeomScalars`), so
  geometries that differ only in scalars share an executable; structure
  fields (core/set/way counts) fix array shapes and group points.
* **device sharding** — each execution bucket's stacked point axis is
  padded to the device count and sharded with
  ``repro.sharding.compat.shard_map_norep``, so an N-device host runs N
  grid points at a time per dispatch.

An executable is therefore keyed by (arch dataflow group, NoC model
group, geometry structure, trace *kind* = shape + insn shape + app
count, probe backend, padded batch size, device count); everything else — policy
choice, NoC choice, timing scalars, addresses, instruction mix,
app-to-core assignment — is data. NoC models stack exactly like
policy families (``NocModel.stack_key``; the built-ins all share one
family), so an (arch zoo x {ideal, crossbar, ring}) grid compiles one
executable per architecture family, not per topology.
Multi-tenant mixes (``repro.core.trace.WorkloadMix``) are ordinary
grid points: same-shape mixes share one executable per dataflow group.
Results are bit-identical to running :func:`repro.core.simulate`
per point (a tier-1 test asserts this), so figures can move freely
between the two.

:meth:`SweepGrid.run` marks its host steps with profiler spans
(``jax.profiler.TraceAnnotation``, :data:`RUN_SPANS`): ``sweep.run``
around the call, ``sweep.prepare`` inside it, then per bucket
``sweep.inputs``, ``sweep.launch``, ``sweep.fetch`` and
``sweep.summarize``. With no profiler running they cost a few tens of
nanoseconds each. :func:`compiled_hlo` gives the optimised HLO of
every executable the process dispatched, whose ``op_name`` metadata
carries the round's stage scopes (``repro.core.simulator.ROUND_STAGES``).
The span and scope names are an interface the benchmark reads.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.geometry import (GeomStructure, GpuGeometry, PAPER_GEOMETRY,
                                 geom_structure, split_geometry)
from repro.core.simulator import (SimResult, Trace, _check_arch, _check_noc,
                                  _sim_core, _summarize, round_signature,
                                  trace_kind)
from repro.core.telemetry import TelemetryConfig
from repro.core.arch import get_arch, registered_archs
from repro.core.noc import get_noc, registered_nocs
from repro.core.probe import check_probe_backend
from repro.sharding.compat import make_mesh_1d, shard_map_norep
from jax.sharding import PartitionSpec as P


class SweepPoint(NamedTuple):
    """One (arch, geometry, trace[, noc[, probe_backend]]) grid point.

    ``noc`` selects the interconnect model (``repro.core.noc``); the
    default ``ideal`` keeps every pre-NoC grid bit-exact.
    ``probe_backend`` selects the L1 probe lowering
    (``repro.core.probe``); backends return bit-identical results, so
    the axis only changes which executable serves the point.
    """
    arch: str
    geom: GpuGeometry
    trace: Trace
    noc: str = "ideal"
    probe_backend: str = "lax"


@dataclasses.dataclass(frozen=True)
class SweepReport:
    """Execution accounting for one :meth:`SweepGrid.run`.

    ``n_executables`` counts the distinct compiled programs the run
    dispatched to; ``n_compiles`` counts how many of those were built
    fresh this run (the rest were warm in the process-wide cache).
    ``n_devices`` counts the devices the buckets' outputs actually
    spanned (read from their shardings, not from the request).
    """
    n_points: int
    n_executables: int
    n_compiles: int
    n_devices: int
    wall_s: float


class SweepRun(NamedTuple):
    results: List[SimResult]     # aligned with SweepGrid.points
    report: SweepReport
    #: per-point ``repro.obs.SimTimeline`` list (aligned with points)
    #: when :meth:`SweepGrid.run` was given a telemetry config
    timelines: Optional[list] = None


#: Host spans of :meth:`SweepGrid.run`, outermost first: ``sweep.run``
#: holds ``sweep.prepare`` and, per bucket, the last four in turn.
RUN_SPANS: Tuple[str, ...] = ("sweep.run", "sweep.prepare", "sweep.inputs",
                              "sweep.launch", "sweep.fetch",
                              "sweep.summarize")
_RUN, _PREPARE, _INPUTS, _LAUNCH, _FETCH, _SUMMARIZE = RUN_SPANS

#: Process-wide {executable key: (jitted callable, abstract arguments)}
#: of the executables dispatched so far, for compile accounting (jit
#: itself also caches; this mirrors its keying) and :func:`compiled_hlo`.
_COMPILED_KEYS: Dict[tuple, tuple] = {}

#: Memoized sharded callables per (group, structure, n_devices).
_EXEC_MEMO: Dict[tuple, object] = {}


def compile_count() -> int:
    """Total sweep executables compiled by this process so far."""
    return len(_COMPILED_KEYS)


def compiled_hlo() -> List[Tuple[str, str]]:
    """(module name, optimised HLO text) of every executable
    :meth:`SweepGrid.run` has dispatched in this process, in first-use
    order.

    Each is lowered again from the abstract arguments (shape, dtype,
    sharding) recorded at its first dispatch and compiled, which is a
    compile or a persistent-cache read: call it after timed work, never
    inside it. The text keeps each instruction's ``op_name`` metadata.
    """
    out = []
    for fn, abstract in _COMPILED_KEYS.values():
        text = fn.lower(abstract).compile().as_text()
        out.append((text.split(None, 2)[1].rstrip(","), text))
    return out


def _abstract(tree):
    """Shape, dtype and (where committed) sharding of every array leaf
    of ``tree``: what ``jit`` lowers the concrete leaves as."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=a.sharding if a.committed else None),
        tree)


def _sharded_executable(group: Tuple[str, ...], nocs: Tuple[str, ...],
                        structure: GeomStructure,
                        n_devices: int, n_apps: int,
                        probe_backend: str = "lax",
                        telemetry: Optional[TelemetryConfig] = None):
    """The jitted, device-sharded, vmapped simulator for one bucket."""
    key = (group, nocs, structure, n_devices, n_apps, probe_backend,
           telemetry)
    fn = _EXEC_MEMO.get(key)
    if fn is None:
        mesh = make_mesh_1d(n_devices, "grid")

        def local_batch(point_arrays):
            return jax.vmap(
                lambda pa: _sim_core(group, nocs, pa, structure,
                                     n_apps, probe_backend,
                                     telemetry))(point_arrays)

        # The grid axis is fully partitioned (every point lives on one
        # device), so the varying-axes check has nothing to prove here;
        # it is off for every bucket.
        fn = jax.jit(shard_map_norep(local_batch, mesh=mesh,
                                     in_specs=P("grid"),
                                     out_specs=P("grid")))
        _EXEC_MEMO[key] = fn
    return fn


def _validate_geom(geom: GpuGeometry) -> None:
    if geom.n_cores % geom.cluster_size:
        raise ValueError(
            f"cluster_size={geom.cluster_size} must divide "
            f"n_cores={geom.n_cores}")


def _canonical_group(archs: Iterable[str]) -> Tuple[str, ...]:
    """A dataflow family as an order-independent executable key.

    Members are ordered by registry position, so grids that name the
    same family in different point orders share one compiled executable
    (and one signature memo entry) instead of recompiling per ordering.
    """
    order = {name: i for i, name in enumerate(registered_archs())}
    return tuple(sorted(archs, key=lambda a: order[a]))


def _canonical_noc_group(nocs: Iterable[str]) -> Tuple[str, ...]:
    """NoC stacking family, ordered by registry position (see above)."""
    order = {name: i for i, name in enumerate(registered_nocs())}
    return tuple(sorted(nocs, key=lambda n: order[n]))


def _stack_groups(names: Iterable[str], stack_key_of, canonical
                  ) -> Dict[str, Tuple[str, ...]]:
    """{name: canonical stacked group} over names sharing a stack_key."""
    by_key: Dict[str, List[str]] = {}
    for name in names:
        fam = by_key.setdefault(stack_key_of(name), [])
        if name not in fam:
            fam.append(name)
    out: Dict[str, Tuple[str, ...]] = {}
    for fam in by_key.values():
        group = canonical(fam)
        for name in fam:
            out[name] = group
    return out


#: Memoized abstract round signatures (eval_shape is cheap, not free).
_SIG_MEMO: Dict[tuple, object] = {}


def _signature(group: Tuple[str, ...], arch: str, structure: GeomStructure,
               round_shape: Tuple[int, int],
               insn_shape: Tuple[int, ...] = (), n_apps: int = 1,
               noc_group: Tuple[str, ...] = ("ideal",),
               noc: str = "ideal", probe_backend: str = "lax"):
    key = (group, arch, structure, round_shape, insn_shape, n_apps,
           noc_group, noc, probe_backend)
    if key not in _SIG_MEMO:
        _SIG_MEMO[key] = round_signature(group, arch, structure,
                                         round_shape, insn_shape, n_apps,
                                         noc_group, noc, probe_backend)
    return _SIG_MEMO[key]


def _bucket_arrays(pts: Sequence[SweepPoint], kind: tuple, split,
                   group: Tuple[str, ...], noc_group: Tuple[str, ...]):
    """One bucket's stacked point arrays, on the device, in the order
    :func:`repro.core.simulator._sim_core` takes them."""
    _, insn_shape, _ = kind
    addr = jnp.asarray(np.stack([p.trace.addr for p in pts]), jnp.int32)
    is_write = jnp.asarray(
        np.stack([p.trace.is_write for p in pts]), bool)
    if insn_shape == ():
        insn = jnp.asarray([p.trace.insn_per_req for p in pts],
                           jnp.float32)
    else:
        insn = jnp.asarray(
            np.stack([p.trace.insn_per_req for p in pts]), jnp.float32)
    core_app = jnp.asarray(
        np.stack([p.trace.core_app_ids for p in pts]), jnp.int32)
    scalars = jax.tree.map(
        lambda *leaves: jnp.stack(leaves),
        *[split(p.geom)[1] for p in pts])
    policy_idx = jnp.asarray(
        [group.index(p.arch) for p in pts], jnp.int32)
    noc_idx = jnp.asarray(
        [noc_group.index(p.noc) for p in pts], jnp.int32)
    return (addr, is_write, insn, core_app, scalars, policy_idx, noc_idx)


class SweepGrid:
    """A cartesian (arch x geometry x noc x trace) grid and its engine.

    ``SweepGrid(archs, geoms, traces, nocs)`` enumerates the full
    product with the trace axis fastest and the arch axis slowest;
    :meth:`from_points` accepts an arbitrary point list instead (the
    engine re-buckets internally either way). :meth:`run` returns the
    per-point :class:`SimResult` list aligned with :attr:`points`, plus
    a :class:`SweepReport`.
    """

    def __init__(self, archs: Sequence[str],
                 geoms: Optional[Sequence[GpuGeometry]] = None,
                 traces: Sequence[Trace] = (),
                 nocs: Sequence[str] = ("ideal",),
                 probe_backends: Sequence[str] = ("lax",)):
        geoms = list(geoms) if geoms is not None else [PAPER_GEOMETRY]
        traces = list(traces)   # tolerate one-shot iterables
        self.points: List[SweepPoint] = [
            SweepPoint(a, g, t, n, pb)
            for a in archs for g in geoms for n in nocs
            for pb in probe_backends for t in traces]
        self._validate()

    @classmethod
    def from_points(cls, points: Iterable[SweepPoint]) -> "SweepGrid":
        grid = cls.__new__(cls)
        grid.points = [SweepPoint(*p) for p in points]
        grid._validate()
        return grid

    def _validate(self) -> None:
        for arch in {p.arch for p in self.points}:
            _check_arch(arch)
        for noc in {p.noc for p in self.points}:
            _check_noc(noc)
        for backend in {p.probe_backend for p in self.points}:
            check_probe_backend(backend)
        seen = set()
        for p in self.points:
            if id(p.geom) not in seen:
                seen.add(id(p.geom))
                _validate_geom(p.geom)
        self._validate_stacking()

    def _noc_group_of(self) -> Dict[str, Tuple[str, ...]]:
        """{noc name: canonical stacked NoC group} over this grid."""
        return _stack_groups({p.noc for p in self.points},
                             lambda n: get_noc(n).stack_key,
                             _canonical_noc_group)

    def _validate_stacking(self) -> None:
        """Reject stack_key families whose members' dataflow diverges.

        Architectures (and NoC models) sharing a ``stack_key`` promise
        an identical round dataflow (same carried state pytree) so the
        engine may compile them into one switch-selected executable. A
        new policy or model that claims an existing family's key but,
        say, threads an extra state array would fail deep inside
        ``lax.switch`` with an opaque shape error — catch it here, per
        (family, geometry structure, round shape) actually swept
        together, with a message that names the offender.
        """
        noc_group_of = self._noc_group_of()
        group_of = _stack_groups(
            dict.fromkeys(p.arch for p in self.points),
            lambda a: get_arch(a).stack_key, _canonical_group)
        for group in {g for g in group_of.values() if len(g) > 1}:
            key = get_arch(group[0]).stack_key
            members = set(group)
            # one representative NoC member per stacked group: whether
            # two archs share a round dataflow cannot depend on which
            # member is selected (the NoC state contribution is
            # group-sized either way), and the NoC-family loop below
            # validates NoC divergence itself — so don't multiply the
            # eval_shape tracings by the NoC axis.
            combos = {(geom_structure(p.geom), p.trace.addr.shape[1:],
                       np.shape(p.trace.insn_per_req), p.trace.n_apps,
                       noc_group_of[p.noc], noc_group_of[p.noc][0],
                       p.probe_backend)
                      for p in self.points if p.arch in members}
            for structure, round_shape, insn_shape, n_apps, ngroup, noc, \
                    backend in combos:
                ref = _signature(group, group[0], structure, round_shape,
                                 insn_shape, n_apps, ngroup, noc, backend)
                for arch in group[1:]:
                    if _signature(group, arch, structure, round_shape,
                                  insn_shape, n_apps, ngroup, noc,
                                  backend) != ref:
                        raise ValueError(
                            f"stack_key {key!r}: architecture {arch!r} "
                            f"does not share {group[0]!r}'s round "
                            "dataflow (state pytrees differ), so they "
                            "cannot stack into one executable; give "
                            f"{arch!r} its own stack_key")
        # NoC families: one fixed architecture per combo, members of the
        # stacked model group must carry identical state pytrees. The
        # groups are exactly the ones run() buckets by, so validation
        # and execution can never disagree on family membership.
        for ngroup in {g for g in noc_group_of.values() if len(g) > 1}:
            key = get_noc(ngroup[0]).stack_key
            members = set(ngroup)
            combos = {(geom_structure(p.geom), p.trace.addr.shape[1:],
                       np.shape(p.trace.insn_per_req), p.trace.n_apps,
                       p.arch, p.probe_backend)
                      for p in self.points if p.noc in members}
            for structure, round_shape, insn_shape, n_apps, arch, backend \
                    in combos:
                agroup = (arch,)
                ref = _signature(agroup, arch, structure, round_shape,
                                 insn_shape, n_apps, ngroup, ngroup[0],
                                 backend)
                for noc in ngroup[1:]:
                    if _signature(agroup, arch, structure, round_shape,
                                  insn_shape, n_apps, ngroup, noc,
                                  backend) != ref:
                        raise ValueError(
                            f"NoC stack_key {key!r}: model {noc!r} does "
                            f"not share {ngroup[0]!r}'s round dataflow "
                            "(carried NoC state pytrees differ), so "
                            "they cannot stack into one executable; "
                            f"give {noc!r} its own stack_key")

    def _prepare(self, n_devices: Optional[int],
                 telemetry: Optional[TelemetryConfig]):
        """(device count, {bucket key: point indices}, geometry split)."""
        if telemetry is not None:
            for p in self.points:
                telemetry.window_for(p.trace.addr.shape[0])
        avail = len(jax.devices())
        D = avail if n_devices is None else n_devices
        if not 1 <= D <= avail:
            raise ValueError(
                f"n_devices={n_devices} but this host has {avail} "
                f"{jax.default_backend()} device(s)")

        # Dataflow groups, ordered by first appearance of each arch;
        # NoC stacking groups the same way.
        group_of = _stack_groups(
            dict.fromkeys(p.arch for p in self.points),
            lambda a: get_arch(a).stack_key, _canonical_group)
        noc_group_of = self._noc_group_of()

        # One geometry split per *unique* geometry, not per point: each
        # split commits the GeomScalars leaves to device.
        splits: Dict[GpuGeometry, tuple] = {}

        def split(geom):
            if geom not in splits:
                splits[geom] = split_geometry(geom)
            return splits[geom]

        # Execution buckets: (group, NoC group, structure, trace kind,
        # probe backend) — kind = (addr shape, insn shape, n_apps), so
        # multi-app mixes bucket apart from solo traces but together
        # with each other (no per-mix recompilation), and stacked NoC
        # models ride the same executable as their family. Probe
        # backends bucket apart: they lower different programs.
        buckets: Dict[tuple, List[int]] = {}
        for i, p in enumerate(self.points):
            key = (group_of[p.arch], noc_group_of[p.noc],
                   split(p.geom)[0], trace_kind(p.trace),
                   p.probe_backend)
            buckets.setdefault(key, []).append(i)
        return D, buckets, split

    def _summarize_bucket(self, idxs, stats, results, timelines,
                          telemetry) -> None:
        """Fill ``results`` (and ``timelines``) at ``idxs`` from one
        bucket's fetched stats."""
        snaps = stats.pop("timeline", None)
        for b, i in enumerate(idxs):
            p = self.points[i]
            results[i] = _summarize(
                jax.tree.map(lambda a: a[b], stats), p.trace)
            if telemetry is not None:
                from repro.obs.timeline import SimTimeline
                timelines[i] = SimTimeline.from_snapshots(
                    jax.tree.map(lambda a: a[b], snaps), telemetry,
                    rounds=p.trace.addr.shape[0],
                    meta={"arch": p.arch, "noc": p.noc,
                          "n_apps": p.trace.n_apps,
                          "n_cores": p.trace.n_cores,
                          "probe_backend": p.probe_backend})

    def run(self, n_devices: Optional[int] = None, *,
            telemetry: Optional[TelemetryConfig] = None) -> SweepRun:
        """Sweep every grid point; one sharded dispatch per bucket.

        ``telemetry`` (static, hashable) threads windowed
        observability through every bucket: the returned
        :class:`SweepRun` gains a per-point ``timelines`` list
        (``repro.obs.SimTimeline``, aligned with :attr:`points`) and
        per-point results stay bit-equal to the default run. ``None``
        reuses exactly the pre-telemetry executables.

        ``n_devices`` (default: every local device) must not exceed the
        devices present; asking for more raises.
        """
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(_RUN):
            with jax.profiler.TraceAnnotation(_PREPARE):
                D, buckets, split = self._prepare(n_devices, telemetry)
            results: List[Optional[SimResult]] = [None] * len(self.points)
            timelines: Optional[list] = (
                [None] * len(self.points) if telemetry is not None
                else None)
            used_execs: set = set()
            spanned: set = set()
            new_compiles = 0
            for (group, noc_group, structure, kind, backend), idxs \
                    in buckets.items():
                with jax.profiler.TraceAnnotation(_INPUTS):
                    B = len(idxs)
                    pad = (-B) % D
                    rows = idxs + [idxs[-1]] * pad      # repeat last point
                    args = _bucket_arrays(
                        [self.points[i] for i in rows], kind, split,
                        group, noc_group)
                with jax.profiler.TraceAnnotation(_LAUNCH):
                    exec_key = (group, noc_group, structure, kind, backend,
                                B + pad, D, telemetry)
                    used_execs.add(exec_key)
                    fn = _sharded_executable(group, noc_group, structure, D,
                                             kind[2], backend, telemetry)
                    if exec_key not in _COMPILED_KEYS:
                        _COMPILED_KEYS[exec_key] = (fn, _abstract(args))
                        new_compiles += 1
                    out = fn(args)
                with jax.profiler.TraceAnnotation(_FETCH):
                    spanned |= out["cycles"].sharding.device_set
                    stats = jax.device_get(out)
                with jax.profiler.TraceAnnotation(_SUMMARIZE):
                    self._summarize_bucket(idxs, stats, results, timelines,
                                           telemetry)

            report = SweepReport(
                n_points=len(self.points),
                n_executables=len(used_execs),
                n_compiles=new_compiles,
                n_devices=len(spanned),
                wall_s=time.perf_counter() - t0,
            )
        return SweepRun(results=results, report=report,  # type: ignore
                        timelines=timelines)
