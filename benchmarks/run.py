"""Benchmark suite: one module per paper table/figure + kernels +
serving (+ the probe-kernel roofline on a TPU). Prints
``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run [--full] [--rounds N] \
      [--report-json PATH] [--serving-json PATH] [--serving-rounds N] \
      [--telemetry OUT_DIR] [--roofline]

JAX's persistent compilation cache is on (``repro.compile_cache``):
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``.

Every figure is timed individually (``figure.<name>.wall_s`` lines)
and run under a failure collector: a figure that raises prints its
traceback, the remaining figures still run, and the process exits
non-zero at the end listing what failed — CI sees every broken figure
in one run instead of one per push.

--telemetry OUT_DIR runs the observability smoke capture
(``benchmarks.telemetry_capture``): one instrumented simulator point
and one instrumented serving replay, writing windowed timelines
(JSON/CSV), Perfetto traces, a run manifest, and a
``kind="telemetry"`` report into OUT_DIR with conservation checked
inline.

--report-json additionally runs the contention-policy-zoo sensitivity
sweep (``repro.core.report``: private/ata/ciao/victim over widened
l1_ways / noc_bw / hide axes) plus the multi-tenant ``mix`` fairness
section (the full zoo over the locality mixes, pairs and a 3-app
point) and the interconnect-topology ``noc`` section (the zoo x
{ideal, crossbar, ring} x noc_bw) and
writes the machine-readable report JSON + markdown table to PATH —
CI's sharded-sweep-smoke job uploads it as an artifact and gates on
drift vs the committed baseline (``benchmarks/baselines/``,
``scripts/check_bench_regression.py``; the gate is schema-versioned,
so a schema-1 baseline still gates the solo cells of a schema-2
report).

--serving-json runs the serving-engine scale grid
(``benchmarks.fig_serving_scale``: shards x traffic mix x serving
policy through the vectorized ``repro.serving.engine``) and writes its
``kind="serving"`` report there; ``--serving-rounds`` fixes the rounds
per stream (CI smoke uses 512 to match
``benchmarks/baselines/serving_rounds512.json``), while the default —
and any ``--full`` run — calibrates rounds so every (shards, mix)
stream replays at least 1,000,000 requests.

--roofline runs the probe-kernel roofline (``benchmarks.roofline``):
the compiled Pallas kernels timed against the published peaks of the
device found. It needs a TPU listed in ``roofline.PEAKS``; anywhere
else the phase fails, and with it the run.

--full uses every per-app kernel (Fig. 9 fidelity); default trims for
CI speed on the 1-core container. --rounds truncates every trace (CI
smoke). The figure sweeps run through ``repro.core.sweep.SweepGrid`` —
same-dataflow architectures stacked into shared executables, stacked
grid points sharded across host devices — and share results via
``benchmarks.common.cached_suite``, so fig10/table1 reuse fig8's
simulations. The ``sweep.executables_compiled`` /
``sweep.figures_total_s`` lines surface sweep-engine perf regressions
in CI logs.
"""
import argparse
import os
import sys
import time
import traceback

#: figures that raised this run; non-empty -> exit code 1 at the end
_FAILURES = []


def _figure(name, fn, *args, **kwargs):
    """Run one figure: time it, survive it, account for it.

    A raising figure prints its traceback to stderr and is recorded in
    ``_FAILURES`` (the suite exits non-zero after the *last* figure),
    so CI surfaces every broken figure in a single run. Returns the
    figure's return value, or None on failure.
    """
    from benchmarks.common import emit
    t0 = time.perf_counter()
    try:
        out = fn(*args, **kwargs)
    except Exception:                       # noqa: BLE001
        wall = time.perf_counter() - t0
        print(f"FIGURE FAILED: {name} after {wall:.2f}s",
              file=sys.stderr)
        traceback.print_exc()
        _FAILURES.append(name)
        emit(f"figure.{name}.wall_s", wall * 1e6, "FAILED")
        return None
    wall = time.perf_counter() - t0
    emit(f"figure.{name}.wall_s", wall * 1e6, f"{wall:.2f}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--rounds", type=int, default=None,
                    help="truncate every trace to N rounds (CI smoke)")
    ap.add_argument("--report-json", default=None, metavar="PATH",
                    help="write the policy-zoo sensitivity report "
                    "(JSON + sibling .md) to PATH")
    ap.add_argument("--serving-json", default=None, metavar="PATH",
                    help="run the serving-engine scale grid and write "
                    "its kind=serving report to PATH")
    ap.add_argument("--serving-rounds", type=int, default=None,
                    help="fixed rounds per serving stream (CI smoke: "
                    "512); default calibrates to >= 1M requests")
    ap.add_argument("--telemetry", default=None, metavar="OUT_DIR",
                    help="run the observability smoke capture and "
                    "write timelines/traces/manifest into OUT_DIR")
    ap.add_argument("--roofline", action="store_true",
                    help="time the compiled probe kernels against the "
                    "device's published peaks (TPU only)")
    args = ap.parse_args()
    del _FAILURES[:]
    k = 0 if args.full else 1
    k9 = 0 if args.full else 3

    print("name,us_per_call,derived")
    import jax
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import (fig8_ipc, fig9_kernels, fig10_latency,
                            fig_mix_fairness, fig_noc_topology,
                            fig_sweep_geometry, kernel_micro, serving_ata,
                            table1_landscape)
    from benchmarks.common import emit
    from repro.core import sweep as sweep_engine
    t0 = time.perf_counter()
    _figure("fig8_ipc", fig8_ipc.run, kernels_per_app=k,
            rounds=args.rounds)
    _figure("fig9_kernels", fig9_kernels.run, kernels_per_app=k9,
            rounds=args.rounds)
    _figure("fig10_latency", fig10_latency.run, kernels_per_app=k,
            rounds=args.rounds)
    _figure("table1_landscape", table1_landscape.run, kernels_per_app=k,
            rounds=args.rounds)
    _figure("fig_sweep_geometry", fig_sweep_geometry.run,
            kernels_per_app=k, rounds=args.rounds)
    _figure("fig_noc_topology", fig_noc_topology.run, kernels_per_app=k,
            rounds=args.rounds)
    # one fairness grid run serves both the figure and (below) the
    # report's mix section — the mixes are never simulated twice
    from repro.core.report import mix_grid_run
    mix_run = _figure("mix_grid", mix_grid_run, rounds=args.rounds)
    if mix_run is not None:
        _figure("fig_mix_fairness", fig_mix_fairness.run,
                kernels_per_app=k, rounds=args.rounds, mix_run=mix_run)
    wall = time.perf_counter() - t0
    # Sweep-engine perf counters: compile count and wall time make
    # executable-churn regressions visible in CI logs.
    emit("sweep.figures_total_s", wall * 1e6, f"{wall:.2f}")
    emit("sweep.executables_compiled", 0.0, sweep_engine.compile_count())
    emit("sweep.devices", 0.0, len(jax.devices()))
    if args.report_json:
        def _sensitivity():
            from repro.core import report as sensitivity
            t0 = time.perf_counter()
            from repro.core.noc import PAPER_NOCS
            rep = sensitivity.run_sensitivity(
                kernels_per_app=None if args.full else 1,
                rounds=args.rounds,
                mix_pairings=sensitivity.MIX_PAIRINGS, mix_run=mix_run,
                noc_models=PAPER_NOCS)
            md_path = sensitivity.write_report(args.report_json, rep)
            emit("sensitivity.cells", (time.perf_counter() - t0) * 1e6,
                 len(rep["cells"]))
            emit("sensitivity.executables", 0.0,
                 rep["sweep"]["n_executables"])
            emit("sensitivity.mix_cells", 0.0, len(rep["mix"]["cells"]))
            emit("sensitivity.mix_executables", 0.0,
                 rep["mix"]["sweep"]["n_executables"])
            emit("sensitivity.noc_cells", 0.0, len(rep["noc"]["cells"]))
            emit("sensitivity.noc_executables", 0.0,
                 rep["noc"]["sweep"]["n_executables"])
            print(f"sensitivity report: {args.report_json} + {md_path}",
                  file=sys.stderr)
        _figure("sensitivity_report", _sensitivity)

    _figure("kernel_micro", kernel_micro.run)
    _figure("serving_ata", serving_ata.run)

    if args.serving_json:
        def _serving_scale():
            from benchmarks import fig_serving_scale
            t0 = time.perf_counter()
            srep = fig_serving_scale.run(rounds=args.serving_rounds,
                                         out_json=args.serving_json)
            emit("serving.cells", (time.perf_counter() - t0) * 1e6,
                 len(srep["cells"]))
            emit("serving.requests_total", 0.0,
                 sum(c["requests"] for c in srep["cells"]))
            print(f"serving report: {args.serving_json}",
                  file=sys.stderr)
        _figure("serving_scale", _serving_scale)

    if args.telemetry:
        def _telemetry():
            from benchmarks import telemetry_capture
            rep = telemetry_capture.capture(args.telemetry,
                                            rounds=args.rounds)
            emit("telemetry.sim_windows", 0.0,
                 rep["sim"]["n_windows"])
            emit("telemetry.serving_p99", 0.0,
                 f"{rep['serving']['p99_latency']:.1f}cyc")
            print(f"telemetry capture: {args.telemetry}",
                  file=sys.stderr)
        _figure("telemetry_capture", _telemetry)

    if args.roofline:
        def _roofline():
            from benchmarks import roofline
            for name, _, _, ai, mem_s, comp_s, bound, meas in \
                    roofline.kernel_table():
                emit(f"roofline.kernel.{name}", meas,
                     f"{bound};ai={ai:.1f};"
                     f"mem={mem_s * 1e6:.2f}us;comp={comp_s * 1e6:.2f}us")
        _figure("roofline", _roofline)

    if _FAILURES:
        print(f"{len(_FAILURES)} figure(s) failed: "
              f"{', '.join(_FAILURES)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
