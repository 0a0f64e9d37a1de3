"""Chip smoke test: both hot paths at full width on a TPU.

    python chip_smoke.py             # one chip: simulator + serving
    python chip_smoke.py --chips 4   # four chips: the sharded sweep only

One process, no subprocesses; it holds the chip for its whole run.

Simulator (one chip): ``run_suite`` over the paper's four architectures
x two high-locality apps (one kernel each) on the Table II machine
(``PAPER_GEOMETRY``) at full trace length, through ``SweepGrid``. The
``ata`` points run again with the compiled Pallas probe
(``probe_backend="pallas"``) and must equal the ``lax`` run exactly.
Every point is then re-simulated per point on the host CPU device
(``simulate``'s own executable, fed inputs committed to the CPU, whose
outputs are checked to live there), the plain reference:
integer counters must match exactly; float32 statistics that differ
(reduction order) are printed with the size of the difference.

Serving (one chip): ``serve_stream`` for ``private``, ``broadcast`` and
``ata`` on a 16-shard ``chat+rag`` stream of at least 200k requests at
B = 4 with the default ``ServingConfig``; then the ``ata`` stream with
the compiled Pallas directory probe, whose counters must equal ``lax``;
then the numpy oracle on one 512-round cell, and every cell of
``benchmarks/baselines/serving_rounds512.json`` replayed and matched
exactly on requests, local and remote hits, recomputed blocks and probe
messages.

``--chips 4``: a 12-point ``SweepGrid`` (private/ata x three paper
geometry variants x two cfd kernels) at ``n_devices=4`` must equal the
same grid at ``n_devices=1`` bit for bit, and its outputs must span the
four devices.

Each phase prints its compile and wall seconds (host clock; set-up
numbers, not device metrics). Any mismatch exits non-zero. The last
line of stdout is ``{"ok": true, "device": {"platform": "tpu", "kind":
..., "count": N}}``. JAX's persistent compilation cache is on
(``repro.compile_cache``): ``$JAX_COMPILATION_CACHE_DIR`` when set,
else ``.jax_cache/`` next to this file.
"""
import argparse
import dataclasses
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402

#: Simulator phase: two high-locality apps, one kernel each.
SIM_APPS = ("cfd", "SN")
#: Serving phase: the 16-shard chat+rag stream at B = 4.
SERVE_SHARDS = 16
SERVE_MIX = ("chat", "rag")
SERVE_SLOTS = 4
SERVE_REQUESTS = 200_000
BASELINE = os.path.join(ROOT, "benchmarks", "baselines",
                        "serving_rounds512.json")
#: SimResult fields that are float32 accumulations of modeled time;
#: every other field is an integer counter (or a host-side ratio of
#: counters) and must match the reference exactly.
FLOAT_FIELDS = {"ipc", "l1_latency", "cycles", "l1_lat_sum",
                "flits_queued", "mean_queue_delay", "max_link_util",
                "mean_link_util"}


class Mismatch(Exception):
    pass


class Phase:
    """Times one phase: wall seconds, and compile seconds from JAX's
    own backend-compile events (an XLA compile, or its read from the
    persistent cache)."""

    compile_s = 0.0

    @classmethod
    def listen(cls):
        def on_event(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                cls.compile_s += duration
        jax.monitoring.register_event_duration_secs_listener(on_event)

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), Phase.compile_s
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            print(f"phase {self.name}: compile_s="
                  f"{Phase.compile_s - self.c0!r}", flush=True)
            print(f"phase {self.name}: wall_s="
                  f"{time.perf_counter() - self.t0!r}", flush=True)


def _flat(result, prefix=""):
    """SimResult (nested NamedTuples) -> {dotted field: value}."""
    out = {}
    for name, value in zip(result._fields, result):
        key = f"{prefix}{name}"
        if isinstance(value, tuple) and hasattr(value, "_fields"):
            out.update(_flat(value, key + "."))
        elif isinstance(value, tuple):
            for i, item in enumerate(value):
                out.update(_flat(item, f"{key}.{i}."))
        else:
            out[key] = value
    return out


def _same(a, b):
    return a == b or (a != a and b != b)          # NaN == NaN


def _check_identical(label, got, want):
    fg, fw = _flat(got), _flat(want)
    bad = [k for k in fw if not _same(fg[k], fw[k])]
    if bad:
        raise Mismatch(f"{label}: fields differ: "
                       + ", ".join(f"{k} {fg[k]!r} != {fw[k]!r}"
                                   for k in bad))


def _check_reference(label, got, want):
    """Counters exact; float32 statistics reported where they differ."""
    fg, fw = _flat(got), _flat(want)
    for key in fw:
        if _same(fg[key], fw[key]):
            continue
        if key.rsplit(".", 1)[-1] in FLOAT_FIELDS:
            diff = abs(fg[key] - fw[key])
            print(f"float_diff {label} {key}: tpu={fg[key]!r} "
                  f"cpu={fw[key]!r} abs={diff!r} "
                  f"rel={diff / max(abs(fw[key]), 1e-30)!r}", flush=True)
        else:
            raise Mismatch(f"{label}: counter {key}: tpu={fg[key]!r} "
                           f"cpu={fw[key]!r}")


def cpu_simulate(arch, trace, geom):
    """``simulate(arch, trace, geom)`` run on the host CPU device.

    The inputs are committed to the CPU, so the executable runs there;
    the outputs' devices are checked so that a TPU result can never be
    compared with itself.
    """
    from repro.core import simulator
    from repro.core.geometry import split_geometry
    cpu = jax.devices("cpu")[0]
    structure, scalars = split_geometry(geom)
    args = jax.device_put(simulator._point_arrays(
        simulator._trace_arrays(trace), scalars), cpu)
    stats = simulator._simulate((arch,), ("ideal",), args, structure,
                                trace.n_apps, "lax", None)
    ran_on = {d for leaf in jax.tree.leaves(stats) for d in leaf.devices()}
    if ran_on != {cpu}:
        raise Mismatch(f"CPU reference ran on {sorted(map(str, ran_on))}")
    return simulator._summarize(jax.device_get(stats), trace)


def simulator_phase():
    from repro.core import PAPER_GEOMETRY, SweepGrid, app_traces, run_suite
    from repro.core.arch import PAPER_ARCHITECTURES
    geom = PAPER_GEOMETRY
    traces = {app: app_traces(app, geom, range(1))[0] for app in SIM_APPS}
    with Phase("sim.suite_lax"):
        suite = run_suite(apps=SIM_APPS, archs=PAPER_ARCHITECTURES,
                          geom=geom, kernels_per_app=1)
        results = {(app, arch): suite[app][arch].per_kernel[0]
                   for app in SIM_APPS for arch in PAPER_ARCHITECTURES}
    print(f"sim: {len(results)} points, "
          f"{traces[SIM_APPS[0]].addr.shape[0]} rounds each", flush=True)
    with Phase("sim.ata_pallas"):
        grid = SweepGrid(["ata"], [geom], [traces[a] for a in SIM_APPS],
                         probe_backends=["pallas"])
        run = grid.run()
    for app, res in zip(SIM_APPS, run.results):
        _check_identical(f"{app}/ata pallas vs lax", res,
                         results[(app, "ata")])
    print(f"sim: ata pallas == lax on {len(SIM_APPS)} apps", flush=True)
    with Phase("sim.cpu_reference"):
        for (app, arch), res in results.items():
            _check_reference(f"{app}/{arch}", res,
                             cpu_simulate(arch, traces[app], geom))
    print("sim: counters equal the per-point CPU simulate reference",
          flush=True)


def _counters(res):
    return (res.n_requests, res.local_hits, res.remote_hits,
            res.recomputed_blocks, res.probe_messages)


def serving_phase():
    from repro.core.trace.serving import ServingMix
    from repro.serving import SERVING_POLICIES, ServingConfig, ref, \
        serve_stream
    mix = ServingMix(SERVE_MIX)
    # ~1 request per shard per round; round up to whole 512-sub-round
    # chunks (B divides them) until the stream holds n_requests
    rounds = 512 * math.ceil(SERVE_REQUESTS / (512 * SERVE_SHARDS))
    with Phase("serve.stream"):
        while True:
            stream = mix.make_stream(n_shards=SERVE_SHARDS, rounds=rounds,
                                     seed=0)
            if stream.n_requests >= SERVE_REQUESTS:
                break
            rounds += 512
        stream = stream.batched(SERVE_SLOTS)
    print(f"serve: {stream.n_requests} requests, {SERVE_SHARDS} shards, "
          f"{rounds} rounds, B={SERVE_SLOTS}", flush=True)
    lax_res = {}
    for policy in SERVING_POLICIES:
        with Phase(f"serve.{policy}_lax"):
            lax_res[policy] = res = serve_stream(policy, stream)
        print(f"serve: {policy}: requests={res.n_requests} "
              f"local={res.local_hits} remote={res.remote_hits} "
              f"recomputed={res.recomputed_blocks} "
              f"probes={res.probe_messages}", flush=True)
        if res.n_requests != stream.n_requests:
            raise Mismatch(f"{policy}: served {res.n_requests} of "
                           f"{stream.n_requests} requests")
    if lax_res["ata"].probe_messages or not \
            lax_res["broadcast"].probe_messages:
        raise Mismatch("probe messages: ata must send none, broadcast "
                       "some")
    with Phase("serve.ata_pallas"):
        res = serve_stream("ata", stream,
                           ServingConfig(probe_backend="pallas"))
    if _counters(res) != _counters(lax_res["ata"]) or not np.array_equal(
            res.shard_load, lax_res["ata"].shard_load):
        raise Mismatch(f"ata pallas {_counters(res)} != lax "
                       f"{_counters(lax_res['ata'])}")
    print("serve: ata pallas == lax", flush=True)

    with Phase("serve.oracle"):
        small = mix.make_stream(n_shards=SERVE_SHARDS, rounds=512, seed=0)
        got = serve_stream("ata", small)
        want = ref.run_stream("ata", ref.AtaCacheConfig(), small)
    if (got.local_hits, got.remote_hits, got.recomputed_blocks,
            got.probe_messages) != (want.local_hits, want.remote_hits,
                                    want.recomputed_blocks,
                                    want.probe_messages):
        raise Mismatch("ata engine != numpy oracle on the 512-round "
                       "stream")
    print(f"serve: engine == numpy oracle ({small.n_requests} requests)",
          flush=True)

    with open(BASELINE) as f:
        base = json.load(f)
    with Phase("serve.baseline512"):
        mixes = {m: ServingMix(tuple(m.split("+")))
                 for m in base["config"]["mixes"]}
        for cell in base["cells"]:
            st = mixes[cell["mix"]].make_stream(
                n_shards=cell["shards"], rounds=cell["rounds"],
                seed=base["config"]["seed"]).batched(cell["slots"])
            res = serve_stream(cell["policy"], st)
            want = (cell["requests"], cell["local_hits"],
                    cell["remote_hits"], cell["recomputed_blocks"],
                    cell["probe_messages"])
            if _counters(res) != want:
                raise Mismatch(f"baseline cell {cell['shards']}/"
                               f"{cell['mix']}/{cell['policy']}/"
                               f"B{cell['slots']}: {_counters(res)} != "
                               f"{want}")
    print(f"serve: {len(base['cells'])} baseline cells matched", flush=True)


def sharded_phase():
    from repro.core import APPS, PAPER_GEOMETRY, SweepGrid, app_traces
    geoms = [PAPER_GEOMETRY,
             dataclasses.replace(PAPER_GEOMETRY, svc_port=4),
             dataclasses.replace(PAPER_GEOMETRY, lat_dram=400)]
    traces = app_traces("cfd", PAPER_GEOMETRY,
                        range(min(2, APPS["cfd"].n_kernels)))
    grid = SweepGrid(("private", "ata"), geoms, traces)
    with Phase("sweep.4dev"):
        wide = grid.run(n_devices=4)
    with Phase("sweep.1dev"):
        one = grid.run(n_devices=1)
    if wide.report.n_devices != 4:
        raise Mismatch(f"outputs spanned {wide.report.n_devices} devices,"
                       " not 4")
    for pt, a, b in zip(grid.points, wide.results, one.results):
        _check_identical(f"{pt.arch} 4-dev vs 1-dev", a, b)
    print(f"sweep: {len(grid.points)} points on 4 devices == 1 device "
          f"({wide.report.n_executables} executables)", flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: simulator + serving; 4: the sharded sweep")
    args = ap.parse_args()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (found {devices[0].platform}); "
              "refusing to run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s)", file=sys.stderr)
        return 1
    print(f"cache_dir {enable_compile_cache(ROOT)}", flush=True)
    Phase.listen()
    try:
        if args.chips == 4:
            sharded_phase()
        else:
            simulator_phase()
            serving_phase()
    except Mismatch as e:
        print(f"chip_smoke: MISMATCH: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
