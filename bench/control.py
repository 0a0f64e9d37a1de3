"""Readings that the limits of ``correct`` are set from.

    python bench/control.py --workload <cell> --seeds 1 2 3 [--control 0|1]
    python bench/control.py --config <config> --traffic <traffic> --seeds ...

For each seed, at the cell's own size: the program's readings (one
work unit on this host's chips, compared with the plain reference) and,
with ``--control 1``, the control's: the reference itself with its
timing in bfloat16, the precision below the configuration's float32,
put in the program's place. A limit lies above every sound reading of
the program and below the control's. One process reads every seed, so
the program compiles once. Prints one line per seed and reading, and a
JSON summary last. The second form reads a configuration and traffic
mix that no cell of ``BENCHMARK.json`` names, on one chip. Needs a
TPU, like ``bench/run.py``.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def readings(config: dict, traffic: dict, seed: int, control: bool) -> dict:
    """{"program": {name: reading}, "control": {...}} for one seed."""
    from bench import harness
    cell = harness.entry_module(config).Cell(config, traffic, seed)
    limits = config["limits"]
    out = {"program": cell.check_views([cell.views(cell.unit())], limits)[0]}
    if control:
        out["control"] = cell.check_views([cell.control_views()], limits)[0]
    return {side: {k: v for k, (v, _) in checks.items()}
            for side, checks in out.items()}


def main(argv=None) -> int:
    import argparse
    import json
    from bench import harness
    from repro.compile_cache import enable_compile_cache
    ap = argparse.ArgumentParser(description="Limit readings for one cell.")
    ap.add_argument("--workload")
    ap.add_argument("--config")
    ap.add_argument("--traffic")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    if args.workload:
        spec = harness.load_json(ROOT, "BENCHMARK.json")
        wl, config, traffic = harness.cell_files(spec, args.workload)
    elif args.config and args.traffic:
        wl = {"name": f"{args.config}/{args.traffic}", "chips": 1}
        config = harness.load_json(harness.BENCH, "configs",
                                   args.config + ".json")
        traffic = harness.load_json(harness.BENCH, "traffic",
                                    args.traffic + ".json")
    else:
        ap.error("give --workload, or --config and --traffic")
    try:
        harness.check_devices(wl["chips"])
    except harness.NoChip as e:
        print(f"control: {e}; refusing to run", file=sys.stderr)
        return 3
    enable_compile_cache(ROOT)
    summary = []
    for seed in args.seeds:
        r = readings(config, traffic, seed, bool(args.control))
        for side, vals in r.items():
            print(f"{wl['name']} seed {seed} {side}: "
                  + " ".join(f"{k}={v!r}" for k, v in vals.items()),
                  flush=True)
        summary.append({"seed": seed, **r})
    print(json.dumps({"workload": wl["name"], "limits": config["limits"],
                      "readings": summary}, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
