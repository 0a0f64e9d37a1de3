"""Vectorized, jitted ATA serving engine with batched admission.

The production-scale replacement for the Python-loop oracle
(``repro.serving.ref``): a :class:`~repro.core.trace.serving.
RequestStream` grid — ``B = stream.slots`` admission slots per shard
per round — is replayed by ``lax.scan``, so millions of requests run
in vectorized steps with no per-request Python.

Round semantics (the oracle's ``run_stream`` is the bit-exact
reference):

1. **Probe** — every arriving request compares its block chain against
   the sub-round-start replicated directory of all shards. Under
   ``ata`` this is the aggregated-tag-array compare the paper builds
   in hardware; the ``ata_tag_probe`` Pallas kernel is a selectable
   backend for it (``lax`` is the fused-XLA default, mirroring
   ``repro.core.probe.PROBE_BACKENDS``).
2. **Walk** — each request reuses its leading hits (prefix semantics);
   reuse of an own-shard block is revalidated against the *live* local
   directory (this shard's own replication inserts can evict a block
   mid-walk), remote presence is vouched for by the probe (remote
   shards never mutate each other's arrays — the local-write rule).
   Under ``ata`` a remote hit replicates into the local directory
   (paper Fig 7(a)); after the first failure all remaining blocks
   recompute and seal locally.
3. **Price** — remote fetches become :class:`~repro.core.noc.
   NocTraffic` (``flits_per_block`` flits from owner to requester)
   through a pluggable :class:`~repro.core.noc.NocModel` whose state
   carries across rounds (crossbar backpressure works); per-request
   latency folds hit/fetch/recompute terms, the broadcast policy's
   probe round trip, and the NoC delay + occupancy.

**Batched round contract** (``B > 1``): each scan step runs ``B``
sequential *sub-rounds* — an inner ``lax.scan`` over the slot axis —
so slot ``b`` probes a directory that already contains slots
``< b``'s replication inserts and the LRU clock ticks once per
sub-round (``t*B + b + 1``). That makes every hit/probe/fetch counter
bit-identical to the slot-sequentialized ``B=1`` replay *by
construction* (property-tested), while the throughput model charges
one round of critical-path latency (``max`` over all ``B×C``
requests) per ``B`` admissions and routes the whole round's remote
fetches through **one** NoC round (slot-major ``B·C·K`` traffic, so
the crossbar's ``group_prefix_sum`` port arbitration orders the
slots' flits exactly like the architecture policies order ports).

Engine internals (the measured ~2x single-round speedup vs the
pre-batching engine):

* the directory is one packed ``(C, S, W, 2)`` int32 array holding
  ``[tag, last-touch]`` lanes — validity is ``tag != 0`` (stream
  hashes are >= 1 by contract), halving the scatter count per walk
  step and shrinking the donated carry to ``{dir, noc, t}``;
* way selection is a single packed-key ``min`` (present < free < LRU,
  ties to the lowest way — first-occurrence semantics identical to
  the previous ``argmax``/``argmin`` chain);
* counters, shard load, and tenant attribution are *derived from the
  emitted per-sub-round outputs* after the scan instead of being
  carried through it, and the per-request latency grid streams back
  to the host where the final sums run in float64/int64 — the int32 /
  f32 overflow-headroom story for nightly-scale runs (the remaining
  device-side int32s — the LRU clock and the packed way key — are
  guarded at config time by :func:`_check_headroom`);
* replay is chunked: fixed-shape chunks of ``_CHUNK_SUBROUNDS``
  sub-rounds run through a **keyed executable cache**
  (:data:`_EXECUTABLES`, keyed by policy x config x slots x stream
  geometry) with ``donate_argnums`` on the carry, so the
  ``{8,16} shards x mixes x 3 policies`` benchmark grid compiles one
  executable per (policy, backend, B) no matter how many rounds each
  cell replays.

Policies: ``private`` (local-only), ``broadcast`` (probe all shards on
local miss — the oracle's ``remote``), ``ata`` (replicated directory,
zero probe messages). The oracle-only ``decoupled`` policy has no
engine analog (its home hash needs int64).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.geometry import GpuGeometry
from repro.core.noc import NocTraffic, get_noc, init_noc_state
from repro.core.telemetry import (TelemetryConfig, hist_quantile,
                                  serving_hist_bins)
from repro.kernels.ata_tag_probe import ata_tag_probe, require_tpu

SERVING_POLICIES = ("private", "broadcast", "ata")

#: Directory-probe backends: fused XLA gather/compare (default), the
#: ``ata_tag_probe`` Pallas kernel compiled by Mosaic (TPU only — asking
#: for it elsewhere raises), and the same kernel interpreted (any
#: backend).
SERVING_PROBE_BACKENDS = ("lax", "pallas", "pallas_interpret")

#: Sub-rounds per compiled chunk. Fixed so every replay of the same
#: (policy, backend, slots, stream geometry) reuses one executable
#: regardless of total rounds; must be divisible by every supported
#: ``slots`` value (powers of two up to ``_MAX_SLOTS`` all divide it).
_CHUNK_SUBROUNDS = 512


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Static engine configuration (hashable: one executable per value).

    The directory mirrors :class:`~repro.serving.ref.AtaCacheConfig`
    (``n_shards`` comes from the stream). Timing terms are abstract
    serving cycles; the NoC scalars feed the
    :class:`~repro.core.geometry.GpuGeometry` the interconnect models
    price traffic with.
    """
    n_sets: int = 64
    n_ways: int = 8
    # --- latency model (cycles per block / per request) -------------
    lat_hit: float = 1.0        # local pool read per block
    lat_fetch: float = 4.0      # remote fetch base per block (+ NoC)
    lat_recompute: float = 40.0  # prefill recompute per block
    lat_probe_rtt: float = 6.0  # broadcast probe round trip per request
    # --- interconnect ----------------------------------------------
    flits_per_block: int = 4
    noc: str = "ideal"
    noc_bw: float = 16.0
    # --- probe backend ---------------------------------------------
    probe_backend: str = "lax"

    def __post_init__(self):
        if self.noc not in ("ideal", "crossbar", "ring"):
            get_noc(self.noc)   # raises with the registered list
        if self.probe_backend not in SERVING_PROBE_BACKENDS:
            raise ValueError(
                f"probe_backend must be one of {SERVING_PROBE_BACKENDS},"
                f" got {self.probe_backend!r}")
        if self.probe_backend == "pallas":
            require_tpu("ServingConfig(probe_backend='pallas')")

    def geometry(self, n_shards: int) -> GpuGeometry:
        """The one-cluster geometry the NoC models price traffic with."""
        return GpuGeometry(n_cores=n_shards, cluster_size=n_shards,
                           l1_sets=self.n_sets, l1_ways=self.n_ways,
                           flits_per_line=self.flits_per_block,
                           noc_bw=self.noc_bw)


class ServeResult(NamedTuple):
    """Aggregate + per-sub-round outputs of one engine replay."""
    policy: str
    n_requests: int
    local_hits: int
    remote_hits: int
    recomputed_blocks: int
    probe_messages: int
    remote_fetch_blocks: int
    directory_sync_entries: int
    shard_load: np.ndarray          # (C,) reuse serves per shard
    latency: np.ndarray             # (T, C) f32 modeled request latency
    served: np.ndarray              # (T, C) bool request present
    tenants: Tuple[str, ...]
    tenant_requests: np.ndarray     # (n_tenants,)
    tenant_hit_blocks: np.ndarray
    tenant_blocks: np.ndarray
    tenant_latency_sum: np.ndarray  # (n_tenants,) f64
    cycles: float                   # sum of per-round critical paths
    slots: int                      # admissions per shard per round (B)
    noc_injected: float
    noc_delivered: float
    noc_queued: float
    #: value-resolved modeled-latency bincount (telemetry runs only)
    lat_hist: Optional[np.ndarray] = None
    #: histogram quantiles reproduce np.percentile exactly (integral
    #: cost model + ideal NoC)
    hist_exact: bool = False

    @property
    def hit_rate(self) -> float:
        tot = self.local_hits + self.remote_hits + self.recomputed_blocks
        return (self.local_hits + self.remote_hits) / max(tot, 1)

    @property
    def request_latencies(self) -> np.ndarray:
        return self.latency[self.served]

    def latency_percentile(self, q: float) -> float:
        if self.lat_hist is not None and self.hist_exact:
            # exact quantile read from the histogram — bit-identical
            # to np.percentile over the materialized latency array
            return hist_quantile(self.lat_hist, q) \
                if self.lat_hist.sum() else 0.0
        lat = self.request_latencies
        return float(np.percentile(lat, q)) if lat.size else 0.0

    @property
    def p50_latency(self) -> float:
        return self.latency_percentile(50.0)

    @property
    def p99_latency(self) -> float:
        return self.latency_percentile(99.0)

    @property
    def requests_per_kcycle(self) -> float:
        """Modeled throughput (requests per 1000 modeled cycles).

        At ``slots = B`` the engine charges one round of critical-path
        latency per ``B`` admissions, so this is where batched
        admission pays off in the model — the machine-portable number
        the CI throughput-ratio gate compares across B.
        """
        return 1e3 * self.n_requests / max(self.cycles, 1e-9)

    @property
    def load_imbalance(self) -> float:
        m = self.shard_load.mean()
        return float(self.shard_load.max() / m) if m else 0.0


def _probe_all(tags, h, set_idx, *, backend):
    """(C, K, C_dir) hits of every request block vs every directory.

    Validity is implied by the packed-directory contract: sealed tags
    are >= 1 and empty ways are 0, while invalid block lanes carry
    hash 0 — so ``tag == hash != 0`` is the whole hit predicate.
    """
    C, K = h.shape
    if backend == "lax":
        g_t = tags[:, set_idx, :]                   # (C_dir, C, K, W)
        hits = ((g_t == h[None, :, :, None]) & (g_t != 0)).any(-1)
        return jnp.transpose(hits, (1, 2, 0))       # (C, K, C_dir)
    R = C * K
    hits, _ = ata_tag_probe(
        set_idx.reshape(R), h.reshape(R), tags, tags != 0, br=R,
        interpret=backend == "pallas_interpret")
    return hits.reshape(C, K, C)


def _make_chunk_fn(policy: str, cfg: ServingConfig, B: int, C: int,
                   K: int,
                   telemetry: Optional[TelemetryConfig] = None):
    """Build the per-chunk scan body for one executable-cache key.

    The returned function replays ``steps`` admission rounds of ``B``
    sub-rounds each: ``(carry, xs) -> (carry, outs)`` with
    ``carry = {dir, noc, t}`` (donated) and ``outs`` the per-chunk
    emissions the host reduces in wide arithmetic.

    ``telemetry`` (static) additionally emits a per-chunk
    value-resolved latency bincount (``hist``, one int32 bucket per
    modeled cycle up to the :func:`_check_headroom` bound, last bucket
    absorbs non-ideal-NoC overflow) and the per-admission-round probe
    message series (``pm_steps``) for the windowed timeline. The
    ``None`` default traces exactly the pre-telemetry chunk program.
    """
    S, W = cfg.n_sets, cfg.n_ways
    geom = cfg.geometry(C)
    noc = get_noc(cfg.noc)
    i32 = jnp.int32
    f32 = jnp.float32
    cidx = jnp.arange(C, dtype=i32)
    karange = jnp.arange(K)
    warange = jnp.arange(W, dtype=i32)

    def sub_round(c, xb):
        """One admission slot across all shards (a B=1 round)."""
        dirr, t = c
        vr, h, nb = xb                   # (C,), (C, K), (C,)
        clock = t + 1
        set_idx = (h % S).astype(i32)
        tags = dirr[..., 0]

        hits = _probe_all(tags, h, set_idx,
                          backend=cfg.probe_backend)  # (C, K, C_dir)
        local_hit = hits[cidx[:, None], karange[None, :], cidx[:, None]]
        bvalid = (karange[None, :] < nb[:, None]) & vr[:, None]
        if policy == "private":
            hit = local_hit
            owner = jnp.broadcast_to(cidx[:, None], (C, K))
        else:
            hit = hits.any(-1)
            owner = jnp.where(local_hit, cidx[:, None],
                              jnp.argmax(hits, axis=-1).astype(i32))
        miss_bcast = bvalid & ~local_hit
        if policy == "broadcast":
            # one broadcast per locally-missing block of the chain
            pm = jnp.sum(miss_bcast.astype(i32)) * (C - 1)
            rtt = miss_bcast.any(-1)
        else:
            pm = i32(0)
            rtt = jnp.zeros((C,), jnp.bool_)

        alive = vr
        n_local = jnp.zeros((C,), i32)
        n_remote = jnp.zeros((C,), i32)
        n_recomp = jnp.zeros((C,), i32)
        srcs, reus, rems = [], [], []
        for k in range(K):               # static unroll over the chain
            bv = bvalid[:, k]
            hh, si = h[:, k], set_idx[:, k]
            ow = owner[:, k]
            row = dirr[cidx, si]                         # (C, W, 2)
            row_t, row_l = row[..., 0], row[..., 1]
            present_way = (row_t == hh[:, None]) & (row_t != 0)
            # packed way key: present (-1) < free (0) < LRU age, ties
            # to the lowest way — first-occurrence order, identical to
            # an argmax(present)/argmax(free)/argmin(last) chain
            sel = jnp.where(present_way, -1,
                            jnp.where(row_t == 0, 0, row_l))
            pk = ((sel + 1) * W + warange).min(-1)
            way = (pk % W).astype(i32)
            present_self = pk < W
            # own-shard reuse revalidates live; remote is probe-vouched
            ok = (ow != cidx) | present_self
            reused = alive & bv & hit[:, k] & ok
            recomp = bv & ~reused
            alive = alive & (~bv | reused)
            local = reused & (ow == cidx)
            remote = reused & ~local
            n_local += local
            n_remote += remote
            n_recomp += recomp
            do_insert = (recomp | remote) if policy == "ata" else recomp
            row_sel = jnp.where(do_insert, cidx, C)      # OOB -> drop
            dirr = dirr.at[row_sel, si, way].set(
                jnp.stack([hh, jnp.full_like(hh, clock)], -1),
                mode="drop")
            srcs.append(ow)
            reus.append(reused)
            rems.append(remote)

        base = (cfg.lat_hit * n_local + cfg.lat_fetch * n_remote
                + cfg.lat_recompute * n_recomp).astype(f32)
        ys = dict(nl=n_local, nr=n_remote, nc=n_recomp, base=base,
                  rtt=rtt, pm=pm,
                  src=jnp.stack(srcs, axis=1),           # (C, K)
                  reu=jnp.stack(reus, axis=1),
                  rem=jnp.stack(rems, axis=1))
        return (dirr, clock), ys

    def step(carry, x):
        """One admission round: B sequential sub-rounds, one NoC round."""
        vr_b, h_b, nb_b = x              # (B, C), (B, C, K), (B, C)
        (dirr, t), ys = jax.lax.scan(
            sub_round, (carry["dir"], carry["t"]), (vr_b, h_b, nb_b))

        # one NoC round carries the whole admission round's fetches,
        # slot-major so port arbitration (crossbar group_prefix_sum)
        # orders earlier slots' flits first
        src = ys["src"].reshape(-1)                      # (B*C*K,)
        traffic = NocTraffic(
            src=src, dst=jnp.tile(jnp.repeat(cidx, K), B),
            cluster=jnp.zeros_like(src),
            flits=jnp.full((B * C * K,), float(cfg.flits_per_block),
                           f32),
            mask=ys["rem"].reshape(-1))
        transit = noc.transit(geom, carry["noc"], traffic)
        noc_extra = (transit.delay + transit.occupancy) \
            .reshape(B, C, K).sum(-1)

        lat = ys["base"] + noc_extra
        lat += cfg.lat_probe_rtt * ys["rtt"].astype(f32)
        lat = jnp.where(vr_b, lat, 0.0)

        new = dict(dir=dirr, noc=transit.state, t=t)
        outs = dict(lat=lat, nl=ys["nl"], nr=ys["nr"], nc=ys["nc"],
                    pm=ys["pm"].sum(),
                    slidx=jnp.where(ys["reu"], ys["src"], C))
        return new, outs

    def chunk(carry, xs):
        carry, ys = jax.lax.scan(step, carry, xs)
        # per-chunk shard-load reduction: one scatter over the chunk's
        # reused blocks (int32 is safe — a chunk is bounded)
        shard_load = jnp.zeros((C + 1,), i32) \
            .at[ys.pop("slidx").reshape(-1)].add(1)[:C]
        outs = dict(ys, pm=ys["pm"].sum(), shard_load=shard_load)
        if telemetry is not None:
            outs["pm_steps"] = ys["pm"]              # (steps,)
            if telemetry.histograms:
                nb = serving_hist_bins(_max_latency(cfg, K))
                idx = jnp.clip(ys["lat"], 0.0, nb - 1).astype(i32)
                outs["hist"] = jnp.zeros((nb,), i32) \
                    .at[idx.reshape(-1)] \
                    .add(xs[0].reshape(-1).astype(i32))
        return carry, outs

    return chunk


def _max_latency(cfg: ServingConfig, K: int) -> float:
    """Per-request modeled-latency bound under an ideal NoC."""
    return K * max(cfg.lat_hit, cfg.lat_fetch, cfg.lat_recompute) \
        + cfg.lat_probe_rtt


def _integral_cost_model(cfg: ServingConfig) -> bool:
    """True when every latency term is a whole number of cycles and
    the NoC adds none — the regime where the value-resolved histogram
    reconstructs ``np.percentile`` exactly."""
    return cfg.noc == "ideal" and all(
        float(v).is_integer() for v in (cfg.lat_hit, cfg.lat_fetch,
                                        cfg.lat_recompute,
                                        cfg.lat_probe_rtt))


#: Keyed executable cache: (policy, cfg, slots, C, K, steps,
#: telemetry) -> the donated-carry chunk executable. All replays
#: sharing a key — every cell of the benchmark grid with the same
#: policy/backend/B/geometry, any number of rounds — reuse one
#: compiled chunk; ``telemetry=None`` keys the pre-telemetry programs.
_EXECUTABLES: Dict[tuple, jax.stages.Compiled] = {}


def _get_executable(policy: str, cfg: ServingConfig, B: int, C: int,
                    K: int, steps: int,
                    telemetry: Optional[TelemetryConfig] = None):
    key = (policy, cfg, B, C, K, steps, telemetry)
    exe = _EXECUTABLES.get(key)
    if exe is None:
        fn = jax.jit(_make_chunk_fn(policy, cfg, B, C, K, telemetry),
                     donate_argnums=(0,))
        sds = jax.ShapeDtypeStruct
        i32, f32 = jnp.int32, jnp.float32
        noc0 = init_noc_state(get_noc(cfg.noc).n_links(cfg.geometry(C)))
        carry_abs = dict(
            dir=sds((C, cfg.n_sets, cfg.n_ways, 2), i32),
            noc=jax.tree.map(lambda a: sds(a.shape, a.dtype), noc0),
            t=sds((), i32))
        xs_abs = (sds((steps, B, C), jnp.bool_),
                  sds((steps, B, C, K), i32),
                  sds((steps, B, C), i32))
        exe = fn.lower(carry_abs, xs_abs).compile()
        _EXECUTABLES[key] = exe
    return exe


def _check_headroom(policy: str, cfg: ServingConfig, T: int, C: int,
                    K: int) -> None:
    """Config-time overflow guards for the device-side narrow types.

    The scan carry keeps only int32 state (the LRU clock and the
    packed way-selection key derived from it); per-chunk emissions are
    int32/f32 but bounded by the fixed chunk shape, and the final
    counter / latency / cycle accumulation runs on the host in int64 /
    float64 — the widened accumulators for nightly-scale runs (>= 1M
    requests x per-request latency approaches 2^31 in 32-bit).
    """
    lim = np.iinfo(np.int32).max
    # LRU clock ticks once per sub-round; way selection packs it as
    # (last + 1) * n_ways + way
    if (T + 2) * cfg.n_ways >= lim:
        raise ValueError(
            f"{T} sub-rounds x {cfg.n_ways} ways overflows the int32 "
            f"packed LRU key; shard the replay below "
            f"{lim // cfg.n_ways - 2} rounds")
    # per-chunk probe-message sum (broadcast worst case) stays int32
    if policy == "broadcast" \
            and _CHUNK_SUBROUNDS * C * K * max(C - 1, 1) >= lim:
        raise ValueError(
            f"broadcast probe messages per {_CHUNK_SUBROUNDS}-sub-round "
            f"chunk overflow int32 at {C} shards x {K} blocks")
    # per-request latency must stay f32-exact for integer cost models
    max_lat = _max_latency(cfg, K)
    if max_lat >= 2.0 ** 24:
        raise ValueError(
            f"per-request latency bound {max_lat:.3g} exceeds the f32 "
            f"integer-exact range (2^24); scale the cost model down")


def serve_stream(policy: str, stream,
                 cfg: ServingConfig = ServingConfig(), *,
                 telemetry: Optional[TelemetryConfig] = None):
    """Replay ``stream`` under ``policy``; returns a :class:`ServeResult`.

    ``stream`` is a :class:`~repro.core.trace.serving.RequestStream`
    (build one with :class:`~repro.core.trace.serving.ServingMix`);
    ``stream.slots`` selects batched admission — counters are
    slot-order exact for every ``B`` (see the module docstring).

    ``telemetry`` (a :class:`~repro.core.telemetry.TelemetryConfig`)
    turns on windowed observability: the return becomes a
    ``(ServeResult, repro.obs.ServeTimeline)`` pair, the result gains
    its device-side latency histogram (``lat_hist`` — percentile
    properties become exact histogram reads under the default integral
    cost model), and all counters stay bit-equal to the
    ``telemetry=None`` replay (the chunk program only *adds*
    emissions). ``None`` compiles and reuses exactly the
    pre-telemetry executables.
    """
    if policy not in SERVING_POLICIES:
        raise ValueError(f"policy must be one of {SERVING_POLICIES}, "
                         f"got {policy!r}")
    T, C, K = stream.hashes.shape
    B = stream.slots
    _check_headroom(policy, cfg, T, C, K)

    # pad the tail with invalid sub-rounds up to a whole chunk: they
    # tick the clock after the last real access (no LRU effect) and
    # carry no requests, so every counter and latency is unchanged
    pad = -T % _CHUNK_SUBROUNDS
    steps = _CHUNK_SUBROUNDS // B

    def padded(a, fill=0):
        if not pad:
            return np.asarray(a)
        return np.concatenate(
            [a, np.full((pad,) + a.shape[1:], fill, a.dtype)])

    n_chunks = (T + pad) // _CHUNK_SUBROUNDS
    shape = (n_chunks, steps, B, C)
    xs_valid = jnp.asarray(padded(stream.valid).reshape(shape))
    xs_hashes = jnp.asarray(padded(stream.hashes).reshape(shape + (K,)))
    xs_blocks = jnp.asarray(padded(stream.n_blocks).reshape(shape))

    exe = _get_executable(policy, cfg, B, C, K, steps, telemetry)
    carry = dict(
        dir=jnp.zeros((C, cfg.n_sets, cfg.n_ways, 2), jnp.int32),
        noc=init_noc_state(get_noc(cfg.noc).n_links(cfg.geometry(C))),
        t=jnp.int32(0))
    lat_parts, nl_parts, nr_parts, nc_parts = [], [], [], []
    probe_messages = 0
    shard_load = np.zeros(C, np.int64)
    with_hist = telemetry is not None and telemetry.histograms
    lat_hist = (np.zeros(serving_hist_bins(_max_latency(cfg, K)),
                         np.int64) if with_hist else None)
    pm_parts = []
    for i in range(n_chunks):
        carry, outs = exe(
            carry, (xs_valid[i], xs_hashes[i], xs_blocks[i]))
        lat_parts.append(np.asarray(outs["lat"]))
        nl_parts.append(np.asarray(outs["nl"]))
        nr_parts.append(np.asarray(outs["nr"]))
        nc_parts.append(np.asarray(outs["nc"]))
        probe_messages += int(outs["pm"])
        shard_load += np.asarray(outs["shard_load"], np.int64)
        if telemetry is not None:
            pm_parts.append(np.asarray(outs["pm_steps"], np.int64))
        if with_hist:
            lat_hist += np.asarray(outs["hist"], np.int64)

    # host-side wide reduction of the emitted per-sub-round grids
    # (int64 / float64 — the overflow-headroom accumulators)
    def grid(parts):   # (n_chunks, steps, B, C) -> (T, C), trimmed
        return np.concatenate(parts).reshape(-1, C)[:T]

    lat = grid(lat_parts)
    nl, nr, nc = grid(nl_parts), grid(nr_parts), grid(nc_parts)
    local_hits = int(nl.sum(dtype=np.int64))
    remote_hits = int(nr.sum(dtype=np.int64))
    recomputed = int(nc.sum(dtype=np.int64))
    served = np.asarray(stream.valid)
    cycles = float(np.sum(
        lat.reshape(-1, B * C).max(axis=1), dtype=np.float64))

    nt = stream.n_tenants
    tidx = np.asarray(stream.tenant)[served]

    def per_tenant(w, dtype=np.int64):
        out = np.zeros(nt, dtype)
        np.add.at(out, tidx, w[served].astype(dtype))
        return out

    ones = np.ones_like(served, np.int64)
    nstate = carry["noc"]
    result = ServeResult(
        policy=policy,
        n_requests=stream.n_requests,
        local_hits=local_hits,
        remote_hits=remote_hits,
        recomputed_blocks=recomputed,
        probe_messages=probe_messages,
        # every remote hit is exactly one remote block fetch
        remote_fetch_blocks=remote_hits,
        # ata: every sealed block rides the periodic delta all-gather
        directory_sync_entries=recomputed if policy == "ata" else 0,
        shard_load=shard_load,
        latency=lat,
        served=served,
        tenants=stream.tenants,
        tenant_requests=per_tenant(ones),
        tenant_hit_blocks=per_tenant(nl + nr),
        tenant_blocks=per_tenant(nl + nr + nc),
        tenant_latency_sum=per_tenant(lat, np.float64),
        cycles=cycles,
        slots=B,
        noc_injected=float(nstate["injected"]),
        noc_delivered=float(nstate["delivered"]),
        noc_queued=float(nstate["queue"].sum()),
        lat_hist=lat_hist,
        hist_exact=with_hist and _integral_cost_model(cfg),
    )
    if telemetry is None:
        return result
    from repro.obs.timeline import ServeTimeline  # obs sits above serving
    pm_rounds = np.concatenate(pm_parts)[:T // B]
    cycles_rounds = np.max(lat.reshape(-1, B * C), axis=1)
    timeline = ServeTimeline.from_grids(
        window=telemetry.window, slots=B, served=served,
        nl=nl, nr=nr, nc=nc, lat=lat, pm_rounds=pm_rounds,
        cycles_rounds=cycles_rounds,
        tenant=np.asarray(stream.tenant), n_tenants=nt,
        hist=lat_hist, hist_exact=result.hist_exact,
        meta={"policy": policy, "slots": B, "shards": C,
              "noc": cfg.noc, "tenants": "+".join(stream.tenants)})
    return result, timeline


def compile_count() -> int:
    """Engine executables compiled so far (CI budgets this)."""
    return len(_EXECUTABLES)
