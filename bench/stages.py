"""Device time per round stage and idle time per sweep host step.

    python bench/stages.py --workload <cell> --seed <n> --seconds <s>

makes one traced run of a sweep cell, as ``bench/run.py --trace 1``
does (its result line is printed first), then prints the split of the
traced window as one JSON line, the last on stdout. It exits 3,
printing no split, where JAX finds no TPU.

The simulator names the stages of its round with ``jax.named_scope``
(``repro.core.simulator.ROUND_STAGES``: ``l1``, with
``repro.core.probe.PROBE_SCOPE`` nested in it, ``l2``, ``fill``,
``noc``, ``timing``), so each operation of the optimised HLO carries
``.../<stage>/...`` in its ``op_name`` metadata. ``SweepGrid.run``
marks its host steps with ``jax.profiler.TraceAnnotation`` spans
(``repro.core.sweep.RUN_SPANS``: ``sweep.run`` holding
``sweep.prepare`` and, per bucket, ``sweep.inputs``, ``sweep.launch``,
``sweep.fetch``, ``sweep.summarize``). This module reads both:

* Device operations come from ``bench.tracing.load``, on the host's
  clock. Each is placed in the ``XLA Modules`` event that holds it,
  moved by the same skew, and matched by (module name, HLO instruction
  name) to the optimised HLO of the executables ``SweepGrid.run``
  dispatched (``repro.core.sweep.compiled_hlo``). The instruction's
  text must agree too: its result shape, opcode and attributes, which
  the event's name and the HLO line print alike. That check also
  breaks ties where two executables share a module name. An operation
  outside every module event (the profiler lost the event) is matched
  by that text over all dispatched executables. An operation's self
  time goes to the innermost stage scope in its ``op_name``, else to
  ``other``. A module the sweep never dispatched (a small staging
  program) is ``other`` too. An operation of a dispatched module that
  cannot be matched makes the whole split ``None``: it is never
  guessed.
* Each idle interval of a device (outside the union of its operations,
  inside ``bench.window``) is cut at the ``sweep.*`` span boundaries,
  and each piece goes to the innermost ``sweep.*`` span holding it;
  pieces under no ``sweep.*`` span go to ``bench``. Where the device
  and host clocks could not be paired, the split is ``None``.

What the reduction used and how long it took is printed on stderr.
"""
from __future__ import annotations

import bisect
import collections
import json
import os
import re
import sys
import time

if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import harness, tracing  # noqa: E402
from repro.core.probe import PROBE_SCOPE as PROBE  # noqa: E402
from repro.core.simulator import ROUND_STAGES as STAGES  # noqa: E402
from repro.core.sweep import RUN_SPANS  # noqa: E402

OTHER = "other"
#: Host spans of ``SweepGrid.run``: the whole call, then its steps.
RUN_SPAN, STEPS = RUN_SPANS[0], RUN_SPANS[1:]
#: Idle time under no ``sweep.*`` span: the benchmark's own loop.
BENCH = "bench"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE = re.compile(r"^(.*)\((\d+)\)$")


def traced_split(spec: dict, name: str, *, seed: int, seconds: float,
                 t0: float, require_chip: bool = True, files=None,
                 out=None, err=None) -> dict:
    """One traced run of sweep cell ``name`` (``bench.harness.run_cell``
    with ``trace=True``, which prints its result line), then the split
    of its window, printed as one JSON line and returned.

    Device times are summed over the cell's devices per simulated
    request of the window, as ``sim.device_ns_per_req`` is; they are
    None where a request failed. Idle times are percent of the traced
    window, averaged over the devices."""
    from repro.core import sweep
    out, err = out or sys.stdout, err or sys.stderr
    wl = (files or harness.cell_files(spec, name))[0]
    result = harness.run_cell(spec, name, seed=seed, seconds=seconds,
                              trace=True, t0=t0, require_chip=require_chip,
                              files=files, out=out, err=err)
    t1 = time.perf_counter()
    hlo = sweep.compiled_hlo()
    t2 = time.perf_counter()
    r = reduce_file(tracing.find_xplane(harness.TRACE_DIR), hlo,
                    range(wl["chips"]), err=err)
    t3 = time.perf_counter()
    _say(err, f"HLO read in {t2 - t1!r} s, trace reduced in {t3 - t2!r} s")
    requests = result["attempted"] if not result["failed"] else 0
    line = {"workload": name, "seed": seed, "correct": result["correct"],
            "device_ns_per_req": result["metrics"].get(
                "sim.device_ns_per_req", {}).get("value"),
            "idle_share": result["metrics"].get(
                "sim.idle_share", {}).get("value"),
            "hlo_s": t2 - t1, "reduce_s": t3 - t2,
            "stages_ns_per_req": None, "self_ns_per_req": None,
            "idle_share_by_span": None, "mapped_share": None,
            "paired": None, "skew_ns": None}
    if r is not None:
        line.update(paired=r["paired"], skew_ns=r["skew_ns"],
                    mapped_share=(100.0 * r["mapped_s"] / r["self_s"]
                                  if r["self_s"] else None))
        if r["stages"] is not None and requests:
            line["stages_ns_per_req"] = {
                k: 1e9 * v / requests for k, v in r["stages"].items()}
            line["self_ns_per_req"] = 1e9 * r["self_s"] / requests
        if r["idle"] is not None:
            line["idle_share_by_span"] = {
                k: 100.0 * v / r["window_s"] for k, v in r["idle"].items()}
    print(json.dumps(line), file=out, flush=True)
    return line


def main(argv=None, t0=None) -> int:
    import argparse
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(
        description="One traced run of a sweep cell, then the split of "
                    "its device time by round stage and of its idle time "
                    "by sweep host step.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = harness.load_json(harness.ROOT, "BENCHMARK.json")
    try:
        traced_split(spec, args.workload, seed=args.seed,
                     seconds=args.seconds, t0=t0)
    except harness.NoChip as e:
        print(f"stages: {e}; refusing to run", file=sys.stderr)
        return 3
    return 0


def parse_hlo(hlo):
    """{module name: [{instruction: (signature, stage)}]}, one dict per
    executable, from (module name, HLO text) pairs."""
    out = collections.defaultdict(list)
    for module, text in hlo:
        table = {}
        for line in text.splitlines():
            m = _INSTR.match(line)
            if m is None:
                continue
            body = line.strip()
            if body.startswith("ROOT "):
                body = body[5:]
            op = _OP_NAME.search(line)
            table[m.group(1)] = (signature(body),
                                 stage_of(op.group(1) if op else ""))
        out[module].append(table)
    return dict(out)


#: Attributes an operation event's name leaves out of its instruction.
_UNNAMED = ("metadata=", "backend_config=", "frontend_attributes=")


def signature(text: str):
    """(name, result shape, opcode, attributes) of an instruction's text:
    the parts the HLO text and a device event's name print alike (the
    event types each operand and leaves out metadata and backend
    configuration)."""
    name, _, rest = text.partition(" = ")
    cut = _balanced(rest, 0) if rest.startswith("(") else rest.find(" ")
    shape, rest = rest[:cut], rest[cut:].lstrip()
    op, paren, rest = rest.partition("(")
    rest = rest[_balanced("(" + rest, 0) - 1:] if paren else ""
    attrs = tuple(a for a in _top_level(rest.lstrip(", "))
                  if a and not a.startswith(_UNNAMED))
    return name.strip().lstrip("%"), shape, op, attrs


def _balanced(text, i):
    """Index just past the bracket group that opens at ``text[i]``."""
    depth, quoted = 0, False
    while i < len(text):
        c = text[i]
        i += 1
        if quoted:
            quoted = c != '"' or text[i - 2] == "\\"
        elif c == '"':
            quoted = True
        elif c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
            if depth == 0:
                return i
    return i


def _top_level(text):
    """``text`` split at its top-level ", "."""
    out, start, i = [], 0, 0
    while i < len(text):
        if text[i] in "([{\"":
            i = (_balanced(text, i) if text[i] != '"'
                 else text.index('"', i + 1) + 1)
            continue
        if text.startswith(", ", i):
            out.append(text[start:i])
            start = i = i + 2
            continue
        i += 1
    out.append(text[start:])
    return out


def stage_of(op_name: str) -> str:
    """The innermost stage scope named in an ``op_name``, else other."""
    for part in reversed(op_name.split("/")):
        if part in STAGES or part == PROBE:
            return part
    return OTHER


class Unclear(Exception):
    """An operation of a dispatched module matches no single stage."""


def _matcher(tables):
    """A memoised (module name, event name) -> stage function, raising
    :class:`Unclear` where the match is not unique or the instruction's
    text differs from the event's.

    An operation outside every module event (where the profiler lost
    the event) is matched by its signature across all the dispatched
    executables; one no executable holds gives None."""
    memo = {}

    def match(module, event):
        key = (module, event)
        if key in memo:
            return memo[key]
        name, sig = _instr(event), signature(event)
        if module is None:
            found = [t[name] for ts in tables.values() for t in ts
                     if t.get(name, (None,))[0] == sig]
            if not found:
                memo[key] = None
                return None
        else:
            found = [t[name] for t in tables[module] if name in t]
        if not found:
            raise Unclear(f"{name} is in no executable named {module}")
        stages = {s for other, s in found if other == sig}
        if not stages:
            raise Unclear(f"the text of {name} differs from every "
                          f"{module} that holds it")
        if len(stages) > 1:
            raise Unclear(f"{name} of {module} matches the stages "
                          f"{sorted(stages)}")
        memo[key] = stages.pop()
        return memo[key]
    return match


def read_profile(path):
    """From the trace: ({device: [(start, end, module name)]} on the
    device's clock, host issue ends, [(start, end, name)] of the
    ``sweep.*`` spans)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    modules, issued, spans = {}, [], []
    for plane in data.planes:
        m = tracing._DEVICE.match(plane.name)
        for line in plane.lines:
            if m and line.name == tracing.MODULES_LINE:
                modules.setdefault(int(m.group(1)), []).extend(
                    (e.start_ns, e.end_ns, e.name) for e in line.events)
            elif not m and plane.name.startswith("/host"):
                for e in line.events:
                    if e.name.startswith("sweep."):
                        spans.append((e.start_ns, e.end_ns, e.name))
                    elif e.name == tracing.ISSUE_EVENT:
                        issued.append(e.end_ns)
    return modules, issued, spans


def reduce_file(path, hlo, devices, err=None):
    """:func:`reduce` of a trace file, the program's HLO alongside."""
    modules, issued, spans = read_profile(path)
    return reduce(tracing.load(path), modules, issued, spans, hlo,
                  devices, err=err)


def reduce(trace, modules, issued, spans, hlo, devices, err=None):
    """Stage and idle split of the traced window over ``devices``.

    ``trace`` is ``bench.tracing.load``'s (operations on the host's
    clock, ``bench.*`` spans); ``modules`` maps each device to its
    ``XLA Modules`` events on the device's own clock; ``issued`` holds
    the host's issue ends; ``spans`` the ``sweep.*`` spans; ``hlo`` the
    (module name, HLO text) pairs of the dispatched executables, or
    None. Returns None where the trace holds no window or no operation
    on those devices, else {"window_s", "busy_s" (list per device),
    "self_s" (sum of operation self times), "mapped_s", "stages"
    ({stage: s} summed over devices, or None), "idle" ({span or
    "bench": s}, mean over devices, or None), "skew_ns" (list per
    device), "paired"}.
    """
    windows = [(s, e) for s, e, n in trace["spans"]
               if n == tracing.WINDOW_SPAN]
    if not windows or not any(trace["ops"].get(d) for d in devices):
        return None
    lo, hi = windows[0]
    tables = parse_hlo(hlo) if hlo is not None else None
    match = _matcher(tables) if tables else None
    stages = dict.fromkeys(STAGES + (PROBE, OTHER), 0.0)
    others, largest = collections.Counter(), collections.Counter()
    idle = dict.fromkeys(STEPS + (RUN_SPAN, BENCH), 0.0)
    segments = _segments([s for s in spans if s[1] > lo and s[0] < hi],
                         lo, hi)
    busy, skews, self_s, mapped_s = [], [], 0.0, 0.0
    paired, dispatched, unclear = True, False, None
    for d in devices:
        mods = sorted(modules.get(d, []))
        pairs = bool(mods) and len(mods) == len(issued)
        paired = paired and pairs
        skew = tracing._skew(sorted(s for s, _, _ in mods), sorted(issued))
        skews.append(skew)
        mods = [(s - skew, e - skew, n) for s, e, n in mods]
        events = tracing._clip(trace["ops"].get(d, []), lo, hi)
        merged = tracing._merge([(s, e) for s, e, _ in events])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for module, group in _by_module(events, mods):
            names = collections.defaultdict(set)
            for _, _, n in group:
                names[_instr(n)].add(n)
            for instr, t in tracing._self_times(group).items():
                name, t = min(names[instr]), t / 1e9
                self_s += t
                stage = None
                if match is not None and (module is None
                                          or module in tables):
                    dispatched = dispatched or module is not None
                    try:
                        if len(names[instr]) > 1:
                            raise Unclear(f"{instr} outside every module "
                                          "event has two texts")
                        stage = match(module, name)
                    except Unclear as e:
                        unclear = unclear or str(e)
                        continue
                if stage is None:
                    stages[OTHER] += t
                    others[instr] += t
                    continue
                mapped_s += t
                stages[stage] += t
                largest[instr, stage] += t
                if stage == OTHER:
                    others[instr] += t
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            for label, t in _cut(segments, gs, ge):
                idle[label] += t / 1e9 / len(devices)
    if tables and not dispatched:
        unclear = unclear or "no module the sweep dispatched ran"
    if match is None:
        stage_out = None
    elif unclear:
        _say(err, f"unclear match, no stage split: {unclear}")
        stage_out = None
    else:
        stage_out = stages
    has_spans = any(label != BENCH for label in segments[1])
    _say(err, f"idle s by span, mean over devices: {idle}")
    _say(err, f"skew {skews} ns; module/issue pairing "
         f"{'held' if paired else 'failed'} ("
         f"{[len(modules.get(d, [])) for d in devices]} module events, "
         f"{len(issued)} issue events); {len(spans)} sweep spans; "
         f"mapped {100.0 * mapped_s / self_s if self_s else 0.0!r}% of "
         f"{self_s!r} s of operation self time; other's largest: "
         f"{others.most_common(5)}")
    _say(err, "largest operations (name, stage): "
         f"{largest.most_common(10)}")
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy, "self_s": self_s,
            "mapped_s": mapped_s, "stages": stage_out,
            "idle": idle if paired and has_spans else None,
            "skew_ns": skews, "paired": paired}


def _say(err, text):
    print(f"stages: {text}", file=err or sys.stderr)


def _instr(event: str) -> str:
    """The HLO instruction name of an operation event's name."""
    return event.split(" = ")[0].lstrip("%")


def _by_module(events, mods):
    """Operations grouped by the module event holding their start:
    [(module name or None, [(start, end, name)])]."""
    starts = [s for s, _, _ in mods]
    groups = collections.defaultdict(list)
    for ev in events:
        i = bisect.bisect_right(starts, ev[0]) - 1
        inside = i >= 0 and ev[0] < mods[i][1]
        groups[i if inside else -1].append(ev)
    out = []
    for i, group in groups.items():
        name = None
        if i >= 0:
            m = _MODULE.match(mods[i][2])
            name = m.group(1) if m else mods[i][2]
        out.append((name, group))
    return out


def _segments(spans, lo, hi):
    """([segment edges], [labels]) cutting [lo, hi] at every span edge,
    each segment labelled by the innermost span holding it (``bench``
    where none does)."""
    edges = sorted({lo, hi} | {min(max(x, lo), hi) for s, e, _ in spans
                               for x in (s, e)})
    labels = []
    for a, b in zip(edges, edges[1:]):
        mid, best = (a + b) / 2, None
        for s, e, name in spans:
            if s <= mid <= e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        labels.append(best[2] if best else BENCH)
    return edges, labels


def _cut(segments, gs, ge):
    """[(label, ns)] of the idle interval [gs, ge] over the segments."""
    edges, labels = segments
    i = max(bisect.bisect_right(edges, gs) - 1, 0)
    out = []
    while i < len(labels) and edges[i] < ge:
        a, b = max(edges[i], gs), min(edges[i + 1], ge)
        if b > a:
            out.append((labels[i], b - a))
        i += 1
    return out


if __name__ == "__main__":
    sys.exit(main())
