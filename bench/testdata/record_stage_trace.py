"""Record the small stage trace that ``tests/bench`` reads.

    python bench/testdata/record_stage_trace.py <out_dir>

On a TPU: one unit of two single-point ``SweepGrid.run`` calls, one
under ``private`` and one under ``ata`` (the paper geometry, SN kernel
0 cut to a few rounds), inside the benchmark's ``bench.window`` /
``bench.unit`` spans, traced as ``bench/harness.py`` traces. Writes
the trace under ``<out_dir>`` and, beside it, ``stages.hlo.json.gz``:
``repro.core.sweep.compiled_hlo()`` with the source-location tables
left out of each text. Copy the ``.xplane.pb``, gzipped, to
``bench/testdata/stages.xplane.pb.gz`` (about 1.5 MB raw, 250 KB
gzipped) and the HLO file to ``bench/testdata/stages.hlo.json.gz``.
"""
import dataclasses
import gzip
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402

from bench import harness, tracing  # noqa: E402

ROUNDS = 8

#: The source-location tables at the head of an HLO module's text, and
#: per-instruction source locations: nothing the reduction reads.
_TABLES = re.compile(r"\n(FileNames|FunctionNames|FileLocations|"
                     r"StackFrames)\n.*?\n\n", re.S)
_SOURCE = re.compile(r' (source_file="[^"]*"|source_line=\d+|'
                     r'source_end_line=\d+|source_column=\d+|'
                     r'source_end_column=\d+|stack_frame_id=\d+)')


def strip_sources(text: str) -> str:
    return _SOURCE.sub("", _TABLES.sub("\n", text))


def main(out_dir):
    from repro.core import SweepGrid, sweep
    from repro.core.trace import APPS, make_trace
    harness.check_devices(1)
    trace = make_trace(dataclasses.replace(APPS["SN"], rounds=ROUNDS))
    grids = [SweepGrid([arch], None, [trace]) for arch in ("private", "ata")]
    for grid in grids:
        grid.run()
    jax.profiler.start_trace(out_dir,
                             profiler_options=harness._profile_options())
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.unit"):
            for grid in grids:
                grid.run()
    jax.profiler.stop_trace()
    hlo = [(m, strip_sources(t)) for m, t in sweep.compiled_hlo()]
    with gzip.open(os.path.join(out_dir, "stages.hlo.json.gz"), "wt") as f:
        json.dump(hlo, f)
    print(tracing.find_xplane(out_dir))


if __name__ == "__main__":
    main(sys.argv[1])
