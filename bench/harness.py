"""The benchmark harness: one run of one cell, driven by data.

Everything that belongs to one cell is found by name:

* ``BENCHMARK.json`` names the cell's configuration, traffic mix and
  chips, and lists the metrics;
* ``bench/configs/<config>.json`` holds the configuration's sizes, the
  entry that runs it (``bench/entries/<entry>.py``) and the limits of the
  comparison that decides ``correct``;
* ``bench/traffic/<traffic>.json`` holds the traffic mix's parameters;
* ``bench/entries/<entry>.py`` builds the cell (traffic from the seed,
  the work unit, the check against the plain reference; a traced run's
  cell, ``Cell(..., traced=True)``, runs the fixed set of work its
  traffic file sizes to fit the profiler) and names the entry's
  ``FAMILY``, which the metric readers key on;
* ``bench/metrics/<metric>.py`` reads one metric from the run, or
  ``bench/metrics/<metric>.json`` names a ``scope`` (device time of the
  operations under that ``jax.named_scope``, ns per request) or a
  ``span`` (device idle time under that program span, % of the traced
  window), which :func:`read_split_metric` reads from
  ``bench/stages.py``'s split of a run whose entry gives its program's
  HLO.

A run: set-up (persistent compile cache, traffic from the seed, one
warm-up unit that compiles every program the window runs), then a
closed loop of work units, back to back, until ``--seconds`` have
passed (the window closes when the last unit started before the
deadline completes), then the comparison with the reference, then one
JSON line on stdout. A traced run's window is one unit of its traced
cell: every operation is an event, and the profiler keeps a fixed
number of them.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """The host has no TPU, or fewer chips than the cell asks for."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_files(spec: dict, name: str):
    """(workload entry, configuration dict, traffic dict) of one cell."""
    wl = {w["name"]: w for w in spec["workloads"]}.get(name)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    return (wl, load_json(ROOT, cfg["file"]),
            load_json(BENCH, "traffic", wl["traffic"] + ".json"))


def cell_metrics(spec: dict, name: str, trace: bool):
    """The metrics a run of cell ``name`` reports, in file order."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if name in m.get("workloads", [name])]


def entry_module(config: dict):
    """The module that runs a configuration's cells (``bench.entries``)."""
    return importlib.import_module("bench.entries." + config["entry"])


def read_metric(metric: dict, ctx: dict, err=None):
    path = os.path.join(BENCH, "metrics", metric["name"])
    if os.path.isfile(path + ".json"):
        return read_split_metric(load_json(path + ".json"), ctx, err)
    mod = load_module(path + ".py",
                      "bench_metric_" + metric["name"].replace(".", "_"))
    return mod.read(ctx)


def read_split_metric(desc: dict, ctx: dict, err=None):
    """A metric its data file describes: the ``scope``'s device time in
    ns per request of the traced window's grid runs, or the % of the
    traced window the devices idle under the ``span``; None without a
    split, or where a request failed."""
    from bench import stages
    r = ctx.get("split")
    if r is None:
        return None
    if "scope" in desc:
        return (None if ctx["failed"]
                else stages.scope_ns_per_req(r, desc["scope"], err))
    return stages.idle_share(r, desc["span"], err)


class CompileClock:
    """Seconds and count of backend compiles (or persistent-cache reads)
    JAX reports while it is open."""

    def __init__(self):
        self.seconds, self.count = 0.0, 0

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def check_devices(chips: int):
    """The local devices, or :class:`NoChip`. Never falls back."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices


def device_block(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": int(max(peaks))}


def run_cell(spec: dict, name: str, *, seed: int, seconds: float,
             trace: bool, t0: float, require_chip: bool = True,
             files=None, out=sys.stdout, err=sys.stderr):
    """One run of cell ``name``; prints the result object and returns
    it with what its metrics were read from.

    ``require_chip=False`` lets a test drive the rest of a run on
    whatever devices JAX has, and ``files`` hands it the cell's
    (workload, configuration, traffic) at a size a test can hold."""
    import jax
    from repro.compile_cache import enable_compile_cache

    wl, config, traffic = files or cell_files(spec, name)
    if require_chip:
        devices = check_devices(wl["chips"])
    else:
        devices = jax.devices()
    print(f"compile cache: {enable_compile_cache(ROOT)}", file=err)
    # every program in the persistent cache, however quick its compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    entry = entry_module(config)

    with CompileClock() as clock:
        with jax.profiler.TraceAnnotation("bench.setup.traffic"):
            cell = entry.Cell(config, traffic, seed, traced=trace)
        with jax.profiler.TraceAnnotation("bench.setup.warm"):
            error = _attempt(cell.unit, err)
        setup_compile_s, setup_compiles = clock.seconds, clock.count
        if trace:
            seconds = 0.0                           # one unit
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(TRACE_DIR,
                                     profiler_options=_profile_options())
        outputs = []
        start = time.perf_counter()
        setup_s = start - t0
        with jax.profiler.TraceAnnotation("bench.window"):
            while error is None:
                with jax.profiler.TraceAnnotation("bench.unit"):
                    answer = []
                    error = _attempt(lambda: answer.append(cell.unit()), err)
                outputs.append(answer[0] if answer else None)
                if time.perf_counter() - start >= seconds:
                    break
        window_s = time.perf_counter() - start
        if trace:
            jax.profiler.stop_trace()
        window_compiles = clock.count - setup_compiles
    if error is not None and not outputs:
        outputs.append(None)      # the warm-up unit never answered
    device = device_block(devices)
    attempted = len(outputs) * cell.requests_per_unit
    requests = sum(o is not None for o in outputs) * cell.requests_per_unit
    print(f"{name}: seed {seed}, {len(outputs)} units of "
          f"{cell.requests_per_unit} requests in {window_s!r} s; set-up "
          f"{setup_s!r} s ({setup_compiles} compiles, {setup_compile_s!r} s);"
          f" compiles in the window: {window_compiles}", file=err)

    ctx = dict(entry=config["entry"], family=getattr(entry, "FAMILY", None),
               setup_s=setup_s, window_s=window_s, units=len(outputs),
               requests=requests, failed=attempted - requests,
               setup_compile_s=setup_compile_s, setup_compiles=setup_compiles,
               window_compiles=window_compiles, trace=None, split=None)
    if trace:
        ctx.update(_reduce_trace(entry, cell, list(range(wl["chips"])),
                                 len(outputs), err))
        if ctx["trace"]:
            busy = ctx["trace"]["busy_s"]
            device["busy_s"] = sum(busy) / len(busy)
            device["window_s"] = ctx["trace"]["window_s"]
    metrics = {}
    for m in cell_metrics(spec, name, trace):
        value = read_metric(m, ctx, err)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # the window is closed and the peak read: now the reference
    checks, failed = cell.check(outputs, config["limits"])
    correct = failed == 0 and all(v <= lim for v, lim in checks.values())
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if ctx["trace"]:
        result["breakdown"] = {"device_ops": ctx["trace"]["device_ops"],
                               "idle_gaps": ctx["trace"]["idle_gaps"]}
    result["checks"] = {k: {"value": _num(v), "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k}: {_num(v)!r} limit {lim!r}", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return result, ctx


def _reduce_trace(entry, cell, devices, units, err) -> dict:
    """The traced window, parsed once: {"trace": ``tracing.reduce``'s,
    "split": ``stages.split``'s where the entry gives its program's
    HLO, "reduce_s": seconds of both, the HLO's read left out}."""
    from bench import stages, tracing
    t = time.perf_counter()
    profile = tracing.load(tracing.find_xplane(TRACE_DIR))
    parse_s = time.perf_counter() - t
    reduced = tracing.reduce(profile, devices)
    split, hlo_s = None, 0.0
    if reduced and hasattr(entry, "compiled_hlo"):
        h = time.perf_counter()
        hlo = entry.compiled_hlo()
        hlo_s = time.perf_counter() - h
        split = stages.split(profile, hlo, devices, cell.run_requests,
                             units, err=err)
    reduce_s = time.perf_counter() - t - hlo_s
    print(f"trace: parsed in {parse_s!r} s, parsed and reduced in "
          f"{reduce_s!r} s; HLO read in {hlo_s!r} s", file=err)
    return {"trace": reduced, "split": split, "reduce_s": reduce_s}


def _attempt(fn, err):
    """Run one unit of the program; a unit that raises never answers.
    Returns the error's text, or None."""
    try:
        fn()
    except Exception:     # the program under test failed: report, go on
        text = traceback.format_exc()
        print(f"bench: the program raised:\n{text}", file=err)
        return text
    return None


def _num(v):
    """JSON has no infinity: an infinite gap prints as a string."""
    return v if math.isfinite(v) else "inf"


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def main(argv=None, t0=None) -> int:
    import argparse
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    spec = load_json(ROOT, "BENCHMARK.json")
    try:
        run_cell(spec, args.workload, seed=args.seed, seconds=args.seconds,
                 trace=bool(args.trace), t0=t0)
    except NoChip as e:
        print(f"bench: {e}; refusing to run", file=sys.stderr)
        return 3
    return 0
