"""Read a profiler trace (``.xplane.pb``) once, and reduce it to the
benchmark's numbers.

Device planes are the profiler's ``/device:TPU:<n>`` planes; their
``XLA Ops`` line holds one event per operation the device ran, and
their ``XLA Modules`` line one per program execution. A device is busy
where any of its operations runs (the union of their intervals) and
idle elsewhere in the traced window. The window and the host spans come
from ``jax.profiler.TraceAnnotation`` spans on the host plane: the
benchmark's own (names starting ``bench.``) and the program's
(``sweep.``). An idle gap of :func:`reduce` is named by the innermost
``bench.*`` span that holds its midpoint; ``bench/stages.py`` splits by
the program's spans.

The device clock runs apart from the host's (about 1.5 ms behind it
on a v5e). Where every program the host issued shows on a device as
one ``XLA Modules`` event, the device's operations and modules are
moved by the median of (module start - host issue end), pairing both in
order. The profiler now and then loses a module event and keeps its
operations: where the module events fall short of the issues, each run
of operations outside every module event, between two, stands for the
lost event, starting at its first operation. Where the starts still do
not match the issues one for one, the clocks stay as recorded.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ISSUE_EVENT = "tpu::System::Execute=>IssueSequencedEvent"
WINDOW_SPAN = "bench.window"
#: Host spans kept: the benchmark's, then the program's.
BENCH_SPANS, SPAN_PREFIXES = "bench.", ("bench.", "sweep.")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _merge(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def load(path: str) -> dict:
    """The one parse of a trace file: {"ops" and "modules": {device:
    [(start, end, name)]}, "spans": [(start, end, name)], "issued":
    [host issue end], "paired" and "skew_ns": {device: ...}}, in
    nanoseconds, on the host's clock where the device's programs pair
    with the host's issues (see the module text)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, modules, spans, issued = {}, {}, [], []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                into = ops if line.name == OPS_LINE else modules
                into.setdefault(int(m.group(1)), []).extend(
                    (e.start_ns, e.end_ns, e.name) for e in line.events)
            elif not m and plane.name.startswith("/host"):
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.start_ns, e.end_ns, e.name))
                    elif e.name == ISSUE_EVENT:
                        issued.append(e.end_ns)
    issued.sort()
    paired, skews = {}, {}
    for d in ops.keys() | modules.keys():
        mods = sorted(modules.get(d, []))
        paired[d], skew = _align(ops.get(d, []), mods, issued)
        skews[d] = skew
        modules[d] = [(s - skew, e - skew, n) for s, e, n in mods]
        ops[d] = [(s - skew, e - skew, n) for s, e, n in ops.get(d, [])]
    return {"ops": ops, "modules": modules, "spans": spans,
            "issued": issued, "paired": paired, "skew_ns": skews}


def by_module(events, mods):
    """Operations grouped by the module event (of ``mods``, sorted)
    holding their start: {(module index, True): ops}, and {(index of the
    module event before them, or -1, False): ops} for those outside
    every module event."""
    starts = [s for s, _, _ in mods]
    groups = collections.defaultdict(list)
    for ev in events:
        i = bisect.bisect_right(starts, ev[0]) - 1
        groups[i, i >= 0 and ev[0] < mods[i][1]].append(ev)
    return groups


def _align(ops, mods, issued):
    """(paired, device clock minus host clock) of one device: its program
    starts against the host's issue ends, in order. The starts are the
    module events', and where those fall short of the issues, also the
    first operation of each run of operations outside every module
    event (a module event the profiler lost)."""
    starts = [s for s, _, _ in mods]
    if len(starts) != len(issued):
        starts = sorted(starts + [min(g)[0] for (_, inside), g in
                                  by_module(ops, mods).items() if not inside])
    paired = bool(starts) and len(starts) == len(issued)
    return paired, _skew(starts, issued)


def _skew(module_starts, issue_ends) -> float:
    """Device clock minus host clock, from programs paired in order."""
    if not module_starts or len(module_starts) != len(issue_ends):
        return 0.0
    diffs = sorted(m - h for m, h in zip(module_starts, issue_ends))
    return diffs[len(diffs) // 2]


def reduce(trace: dict, devices, top: int = 10) -> dict:
    """Busy, idle, top operations and named idle gaps over ``devices``.

    Times are seconds, averaged over the devices. Returns None where the
    trace holds no window span or no operation on those devices.
    """
    windows = [(s, e) for s, e, n in trace["spans"] if n == WINDOW_SPAN]
    if not windows or not any(trace["ops"].get(d) for d in devices):
        return None
    lo, hi = windows[0]
    spans = sorted((s, e, n) for s, e, n in trace["spans"]
                   if n.startswith(BENCH_SPANS) and n != WINDOW_SPAN
                   and e > lo and s < hi)
    busy, op_time, gap_time = [], collections.Counter(), collections.Counter()
    n = len(devices)
    for d in devices:
        events = _clip(trace["ops"].get(d, []), lo, hi)
        merged = _merge([(s, e) for s, e, _ in events])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for name, t in _self_times(events).items():
            op_time[name] += t / 1e9 / n
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                gap_time[_span_at(spans, (gs + ge) / 2)] += (ge - gs) / 1e9 / n
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy,
        "device_ops": op_time.most_common(top),
        "idle_gaps": gap_time.most_common(top),
    }


def _clip(events, lo, hi):
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def _self_times(events) -> collections.Counter:
    """Time per operation name outside the operations nested in it (a
    loop's own time, not its body's), names cut to the HLO name."""
    out = collections.Counter()
    stack = []                        # [end, name, start, nested time]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            _close(stack.pop(), out)
        if stack:
            stack[-1][3] += e - s
        stack.append([e, name, s, 0])
    while stack:
        _close(stack.pop(), out)
    return out


def _close(frame, out):
    end, name, start, nested = frame
    out[name.split(" = ")[0].lstrip("%")] += end - start - nested


def _span_at(spans, t) -> str:
    """Innermost benchmark span holding time ``t`` (the window if none)."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else WINDOW_SPAN
