"""Run manifests: what produced this report, on what, at what cost.

Every benchmark report (``sensitivity`` / ``simspeed`` / ``serving``
and the telemetry capture) attaches a ``manifest`` block so a number
in ``bench_history/`` can always be traced back to the code revision,
jax version, device (platform, kind and count as ``jax.devices()``
reports them), compile activity, and phase wall-clock that produced
it. A missing git binary or a detached worktree degrades to a ``None``
sha, and an XLA backend without cost analysis to ``None`` costs; a
failure to reach jax's devices fails the run.

The regression gates (``repro.core.report.compare_*``) iterate only
the baseline's sections, so adding ``manifest`` to reports is
forward-compatible with committed baselines by construction.
"""
from __future__ import annotations

import contextlib
import os
import platform
import subprocess
import sys
import time
from typing import Dict, Optional


def git_sha(cwd: Optional[str] = None) -> Optional[str]:
    """HEAD commit sha of the repo containing this package, or None."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=cwd or os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10)
    except (OSError, subprocess.SubprocessError):
        return None
    sha = out.stdout.strip()
    return sha if out.returncode == 0 and sha else None


class PhaseTimer:
    """Wall-clock accounting per named phase of a benchmark run.

    >>> timer = PhaseTimer()
    >>> with timer.phase("sweep"):
    ...     run_the_sweep()
    >>> timer.phases
    {'sweep': 1.234}

    Re-entering a phase name accumulates into it.
    """

    def __init__(self) -> None:
        self.phases: Dict[str, float] = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.phases[name] = self.phases.get(name, 0.0) \
                + (time.perf_counter() - t0)


def _compile_counts() -> Dict[str, int]:
    from repro.core import sweep
    from repro.serving import engine
    return {"sweep": sweep.compile_count(),
            "serving": engine.compile_count()}


def serving_executable_costs() -> Dict[str, dict]:
    """XLA cost analysis (FLOPs / bytes accessed) per cached serving
    executable, keyed by a readable (policy, B, C, K) label."""
    from repro.serving import engine
    costs: Dict[str, dict] = {}
    for key, exe in engine._EXECUTABLES.items():
        policy, _cfg, B, C, K = key[0], key[1], key[2], key[3], key[4]
        label = f"{policy}/B{B}/C{C}/K{K}"
        try:
            ca = exe.cost_analysis()
            costs[label] = {
                "flops": float(ca.get("flops", 0.0)),
                "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            }
        except Exception:
            costs[label] = {"flops": None, "bytes_accessed": None}
    return costs


def run_manifest(phases: Optional[Dict[str, float]] = None,
                 extra: Optional[dict] = None) -> dict:
    """The manifest block attached to benchmark reports."""
    import jax
    devices = jax.devices()
    manifest: dict = {
        "git_sha": git_sha(),
        "python": platform.python_version(),
        "platform": platform.platform(),
        "argv": list(sys.argv),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "compile_counts": _compile_counts(),
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }
    costs = serving_executable_costs()
    if costs:
        manifest["serving_executable_costs"] = costs
    if phases:
        manifest["phases_wall_s"] = {k: round(v, 6)
                                     for k, v in phases.items()}
    if extra:
        manifest.update(extra)
    return manifest
