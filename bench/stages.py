"""Device time by scope and idle time by host span, from a traced window.

The simulator names the stages of its round with ``jax.named_scope``
(``repro.core.simulator.ROUND_STAGES``: ``l1``, with
``repro.core.probe.PROBE_SCOPE`` nested in it, ``l2``, ``fill``,
``noc``, ``timing``), so each operation of the optimised HLO carries
``.../<stage>/...`` in its ``op_name`` metadata. ``SweepGrid.run``
marks its host steps with ``jax.profiler.TraceAnnotation`` spans
(``repro.core.sweep.RUN_SPANS``: ``sweep.run`` holding
``sweep.prepare`` and, per bucket, ``sweep.inputs``, ``sweep.launch``,
``sweep.fetch``, ``sweep.summarize``). :func:`split` reads both from
the one ``bench.tracing.load`` of a traced run:

* Each operation is placed in the ``XLA Modules`` event that holds it
  and matched by (module name, HLO instruction name) to the optimised
  HLO of the executables ``SweepGrid.run`` dispatched
  (``repro.core.sweep.compiled_hlo``). The instruction's text must
  agree too: its result shape, opcode and attributes, which the
  event's name and the HLO line print alike. That check also breaks
  ties where two executables share a module name. An operation of a
  dispatched module that cannot be matched leaves every scope unread:
  it is never guessed. A scope's time is the self time of the matched
  operations whose ``op_name`` holds the scope as a component, so
  nested scopes count (``l1`` holds ``probe``).
* The divisor is the requests of the window's grid runs that the
  trace holds whole. A traced window runs one unit of the cell's traced
  runs, a fixed set the entry sizes to fit under the profiler's cap on
  events (the profiler keeps a window's first events and drops the
  rest). Each ``sweep.launch`` span dispatches one execution of a
  dispatched executable on each device, in order: a module event, or
  operations outside every module event, between two, whose event the
  profiler lost (matched by their text over every dispatched
  executable). Each execution belongs to the ``sweep.run`` span that
  holds its launch. Below its cap the profiler still loses events now
  and then: an execution holding fewer operation events than another
  of its program lost some, whose time would go to the loop that holds
  them, so its grid run is left out of the scopes, its time and its
  requests both. No scope is read where the executions are not as many
  as the launches, where the module events and those lost are fewer
  than the programs the host issued (the profiler cut the trace),
  where the ``sweep.run`` spans do not make whole units, or where no
  run is held whole.
* Each idle interval of a device (outside the union of its operations,
  inside ``bench.window``) is cut at the program's span boundaries,
  and each piece goes to the innermost program span holding it; pieces
  under none go to ``bench``. It needs the device's clock on the
  host's, which ``tracing.load`` gives where every program the host
  issued shows as a module event, or as operations outside every
  module event where the profiler lost one; elsewhere there is no idle
  split.

    python bench/stages.py --workload <cell> --seed <n> --seconds <s>

makes one traced run of a sweep cell, as ``bench/run.py --trace 1``
does (its result line is printed first), then prints the split that
run read as one JSON line, the last on stdout. It exits 3, printing no
split, where JAX finds no TPU.
"""
from __future__ import annotations

import bisect
import collections
import functools
import json
import os
import re
import sys
import time

if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import harness, tracing  # noqa: E402

#: Host spans of ``SweepGrid.run``: the whole call, and one dispatch.
RUN_SPAN, LAUNCH_SPAN = "sweep.run", "sweep.launch"
#: Idle time under no program span: the benchmark's own loop.
BENCH = "bench"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE = re.compile(r"^(.*)\((\d+)\)$")


def traced_split(spec: dict, name: str, *, seed: int, seconds: float,
                 t0: float, require_chip: bool = True, files=None,
                 out=None, err=None) -> dict:
    """One traced run of sweep cell ``name`` (``bench.harness.run_cell``
    with ``trace=True``, which prints its result line), then the split
    that run read, printed as one JSON line and returned.

    Device times are ns per simulated request of the window's grid
    runs, summed over the cell's devices, for each stage scope
    the dispatched HLO names; ``other`` is the operations' self time
    outside the top-level stages. Idle times are percent of the traced
    window, averaged over the devices."""
    from repro.core.probe import PROBE_SCOPE
    from repro.core.simulator import ROUND_STAGES
    out, err = out or sys.stdout, err or sys.stderr
    result, ctx = harness.run_cell(
        spec, name, seed=seed, seconds=seconds, trace=True, t0=t0,
        require_chip=require_chip, files=files, out=out, err=err)
    r = ctx.get("split")
    line = {"workload": name, "seed": seed, "correct": result["correct"],
            "device_ns_per_req": result["metrics"].get(
                "sim.device_ns_per_req", {}).get("value"),
            "idle_share": result["metrics"].get(
                "sim.idle_share", {}).get("value"),
            "reduce_s": ctx.get("reduce_s"), "requests": None,
            "stages_ns_per_req": None, "idle_share_by_span": None,
            "mapped_share": None, "paired": None, "skew_ns": None}
    if r is not None:
        line.update(paired=r["paired"], skew_ns=r["skew_ns"],
                    requests=r["requests"],
                    mapped_share=(100.0 * r["mapped_s"] / r["self_s"]
                                  if r["self_s"] else None))
        per_req = {s: scope_ns_per_req(r, s, err)
                   for s in ROUND_STAGES + (PROBE_SCOPE,) if s in r["named"]}
        if None not in per_req.values() and not result["failed"]:
            per_req["other"] = 1e9 * sum(r["scopes"].values()) / r[
                "requests"] - sum(per_req.get(s, 0.0) for s in ROUND_STAGES)
            line["stages_ns_per_req"] = per_req
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


def scope_ns_per_req(r: dict, scope: str, err=None):
    """Self time of the operations under ``scope`` in the traced window,
    summed over devices, in ns per request of its grid runs; 0.0 where
    none of them ran in the window. None where the split read
    no scope or the operations of one text disagree on the scope; a
    scope no operation of the dispatched HLO names reads None, with a
    line on ``err``."""
    if scope not in r["named"]:
        _say(err, f"no operation of the dispatched HLO names the scope "
             f"{scope!r}")
        return None
    if r["scopes"] is None:
        return None
    total = 0.0
    for op_names, t in r["scopes"].items():
        under = {scope in n.split("/") for n in op_names}
        if len(under) > 1:
            _say(err, f"operations of one text are and are not under "
                 f"{scope!r}: {sorted(op_names)}")
            return None
        if under.pop():
            total += t
    return 1e9 * total / r["requests"]


def idle_share(r: dict, span: str, err=None):
    """Percent of the traced window in which the devices are idle with
    ``span`` the innermost program span, averaged over devices; None
    without paired clocks, or where no such span was traced."""
    if r["idle"] is None:
        return None
    if span not in r["idle"]:
        _say(err, f"no {span!r} span in the traced window")
        return None
    return 100.0 * r["idle"][span] / r["window_s"]


def parse_hlo(hlo):
    """{module name: [{instruction: (signature, op_name)}]}, one dict per
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
            table[m.group(1)] = (signature(body), op.group(1) if op else "")
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


class Unclear(Exception):
    """An operation of a dispatched module matches no instruction."""


def _matcher(tables):
    """A memoised (module name, event name) -> frozenset of the
    ``op_name``s of the instructions it matches, raising
    :class:`Unclear` where no executable of that module name holds the
    instruction with the event's text. An operation outside every
    module event (module None: the profiler lost the event) is matched
    by its text over every executable; one that none holds gives None."""
    memo = {}

    def match(module, event):
        key = (module, event)
        if key not in memo:
            name, sig = _instr(event), signature(event)
            found = [t[name] for ts in (tables.values() if module is None
                                        else [tables[module]])
                     for t in ts if name in t]
            names = frozenset(o for s, o in found if s == sig)
            if module is not None and not found:
                raise Unclear(f"{name} is in no executable named {module}")
            if module is not None and not names:
                raise Unclear(f"the text of {name} differs from every "
                              f"{module} that holds it")
            memo[key] = names or None
        return memo[key]
    return match


def split(trace, hlo, devices, run_requests, units, err=None):
    """Scope and idle split of the traced window over ``devices``.

    ``trace`` is ``bench.tracing.load``'s; ``hlo`` the (module name, HLO
    text) pairs of the dispatched executables; ``run_requests`` the
    requests of each ``SweepGrid.run`` of a unit, in order, and
    ``units`` the units the window ran. Returns None where the trace
    holds no window or no operation on those devices, else
    {"window_s", "self_s" (every operation's self time, summed over
    devices), "mapped_s" (of it, matched to the HLO), "scopes"
    ({frozenset of op_names: s} of the runs held whole, summed over
    devices, or None), "named" (every component of an op_name in the
    HLO), "requests" (of the runs held whole, or None), "idle" ({span
    or "bench": s}, mean over devices, or None), "skew_ns", "paired"}.
    """
    windows = [(s, e) for s, e, n in trace["spans"]
               if n == tracing.WINDOW_SPAN]
    if not windows or not any(trace["ops"].get(d) for d in devices):
        return None
    lo, hi = windows[0]
    tables = parse_hlo(hlo)
    match = _matcher(tables)
    spans = [s for s in trace["spans"] if not s[2].startswith(
        tracing.BENCH_SPANS) and s[1] > lo and s[0] < hi]
    runs = sorted(s for s, _, n in spans if n == RUN_SPAN)
    launches = sorted(s for s, _, n in spans if n == LAUNCH_SPAN)
    issued = len(trace["issued"])
    by_run = collections.defaultdict(collections.Counter)
    partial = collections.defaultdict(list)
    self_s = mapped_s = 0.0
    why = None
    if not run_requests or len(runs) != units * len(run_requests):
        why = (f"{len(runs)} {RUN_SPAN} spans for {units} units of "
               f"{len(run_requests or ())} grid runs")
    for d in devices:
        mods = trace["modules"].get(d, [])
        execs, lost, t, m, unclear = _executions(
            tracing._clip(trace["ops"].get(d, []), lo, hi), mods, tables,
            match)
        self_s, mapped_s = self_s + t, mapped_s + m
        if unclear:
            why = why or f"unclear match: {unclear}"
        elif len(execs) != len(launches):
            why = why or (f"{len(execs)} executions of dispatched programs "
                          f"for {len(launches)} launches")
        elif len(mods) + lost != issued:
            why = why or (f"{len(mods)} module events and {lost} lost for "
                          f"{issued} programs issued: the profiler cut the "
                          "trace")
        if why is not None:
            continue
        whole = _whole_counts(execs)
        for launch, (program, count, scoped, held_by) in zip(launches,
                                                             execs):
            run = bisect.bisect_right(runs, launch) - 1
            by_run[run].update(scoped)
            if not any(count == whole[x] for x in held_by):
                partial[run].append((program, count))
        if -1 in by_run:
            why = f"a {LAUNCH_SPAN} span before every {RUN_SPAN} span"
    kept = [j for j in range(len(runs)) if j not in partial]
    if why is None and partial:
        _say(err, f"runs {sorted(partial)} of {len(runs)} left out of the "
             f"scopes: the profiler lost events inside their executions "
             f"(module event, operation events: {dict(partial)}; whole: "
             f"{whole})")
        if not kept:
            why = "every grid run lost events"
    if why is not None:
        _say(err, f"no scope read: {why}")
    scopes = None
    if why is None:
        scopes = collections.Counter()
        for j in kept:
            scopes.update(by_run[j])
    paired = all(trace["paired"].get(d, False) for d in devices)
    skews = [trace["skew_ns"].get(d, 0.0) for d in devices]
    idle = _idle(trace, devices, spans, lo, hi) if paired and spans else None
    requests = None if why else sum(
        run_requests[j % len(run_requests)] for j in kept)
    _say(err, f"idle s by span, mean over devices: {idle}")
    _say(err, f"skew {skews} ns; module/issue pairing "
         f"{'held' if paired else 'failed'}; "
         f"{[len(trace['ops'].get(d, [])) for d in devices]} operation "
         f"events; {len(spans)} program spans; "
         f"requests {requests}; mapped "
         f"{100.0 * mapped_s / self_s if self_s else 0.0!r}% of "
         f"{self_s!r} s of operation self time")
    return {"window_s": (hi - lo) / 1e9, "self_s": self_s,
            "mapped_s": mapped_s, "scopes": scopes,
            "named": {c for ts in tables.values() for t in ts
                      for _, o in t.values() for c in o.split("/")},
            "requests": requests, "idle": idle, "skew_ns": skews,
            "paired": paired}


def _whole_counts(execs):
    """{executable: operation events of a whole execution} of one
    device's executions. The dispatched programs have no data-dependent
    control flow, so every execution of one executable runs as many
    operations, and the profiler only loses events: an executable's
    whole count is the most events of any execution its HLO holds."""
    whole = collections.Counter()
    for _, count, _, held_by in execs:
        for x in held_by:
            whole[x] = max(whole[x], count)
    return whole


def _executions(ops, mods, tables, match):
    """([(module event name, or None where the profiler lost it;
    operation events; {op_names: self s}; the executables, as (module
    name, index), whose HLO holds every operation's text)] of the
    executions of dispatched executables on the device, in time order;
    module events lost; self s of every operation; of it s matched; the
    first unclear match or None). Operations outside every module
    event, between two, are one execution whose module event the
    profiler lost."""
    sig = functools.lru_cache(maxsize=None)(signature)
    execs, lost = [], 0
    self_s = mapped_s = 0.0
    unclear = None
    groups = tracing.by_module(ops, mods)
    for (i, inside), group in sorted(groups.items(),
                                     key=lambda kv: min(kv[1])[0]):
        module = _module_name(mods[i][2]) if inside else None
        lost += not inside
        names = collections.defaultdict(set)
        for _, _, name in group:
            names[_instr(name)].add(name)
        scoped = collections.Counter()
        for instr, t in tracing._self_times(group).items():
            t /= 1e9
            self_s += t
            if inside and module not in tables:
                continue                  # a program the sweep never ran
            try:
                if len(names[instr]) > 1:
                    raise Unclear(f"{instr} outside every module event has "
                                  "two texts")
                op_names = match(module, min(names[instr]))
            except Unclear as e:
                unclear = unclear or str(e)
                continue
            if op_names is not None:
                mapped_s += t
                scoped[op_names] += t
        if (module in tables) if inside else scoped:
            texts = [min(n) for n in names.values()]
            execs.append((mods[i][2] if inside else None, len(group),
                          scoped, frozenset(
                (m, k) for m, ts in tables.items()
                if not inside or m == module for k, t in enumerate(ts)
                if all(t.get(_instr(x), (None,))[0] == sig(x)
                       for x in texts))))
    return execs, lost, self_s, mapped_s, unclear


def _idle(trace, devices, spans, lo, hi):
    """{innermost program span or "bench": idle s, mean over devices}
    over [lo, hi] of the host's clock."""
    segments = _segments(spans, lo, hi)
    idle = dict.fromkeys(segments[1], 0.0)
    for d in devices:
        ops = tracing._clip(trace["ops"].get(d, []), lo, hi)
        merged = tracing._merge([(s, e) for s, e, _ in ops])
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            for label, t in _cut(segments, gs, ge):
                idle[label] += t / 1e9 / len(devices)
    return idle


def _say(err, text):
    print(f"stages: {text}", file=err or sys.stderr)


def _instr(event: str) -> str:
    """The HLO instruction name of an operation event's name."""
    return event.split(" = ")[0].lstrip("%")


def _module_name(event: str) -> str:
    """A module event's name without its program fingerprint."""
    m = _MODULE.match(event)
    return m.group(1) if m else event


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
