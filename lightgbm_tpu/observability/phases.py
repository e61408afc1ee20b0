"""Attributable per-phase device timing (``PhaseBreakdown``).

The bench's ``phase_timings`` are a CONSUMER of the observability
subsystem, not a parallel implementation: ``to_dict()`` output is byte-compatible with the historical
BENCH json schema (the BENCH_r* trajectory scripts parse it), and every
breakdown also lands in the process-wide metrics registry as
``phase.<name>.*`` gauges so a live snapshot sees the same numbers the
bench prints.

    pb = PhaseBreakdown("headline")
    with pb.compile_window():      # warm-up: compiles allowed
        ...
    with pb.steady_window(iters=12):
        ...
    pb.attach_guard(guard.report())
    json["phase_timings"]["headline"] = pb.to_dict()

Recompile/host-sync counts come from a ``RecompileGuard.report()``
(analysis/guards.py) — the guard itself publishes its totals to the
registry on exit, so ``attach_guard`` only carries them into this phase's
dict and gauges (no double counting of registry counters).
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict


class PhaseBreakdown:
    """Compile/warm-up wall-clock vs steady-state wall-clock vs host-sync +
    recompile counts for one named bench phase (docs/TPU-Performance.md)."""

    def __init__(self, name: str):
        self.name = name
        self.compile_s = 0.0
        self.steady_s = 0.0
        self.steady_iters = 0
        self.guard_report: Dict = {}

    @contextlib.contextmanager
    def compile_window(self):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.compile_s += time.perf_counter() - t0

    @contextlib.contextmanager
    def steady_window(self, iters: int = 0):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.steady_s += time.perf_counter() - t0
            self.steady_iters += iters

    def attach_guard(self, report: Dict) -> None:
        """Fold in a RecompileGuard report (host_syncs / cache misses)."""
        self.guard_report = report or {}

    def to_dict(self) -> Dict:
        out = {"compile_s": round(self.compile_s, 3),
               "steady_s": round(self.steady_s, 3),
               "steady_iters": self.steady_iters}
        if self.steady_iters and self.steady_s:
            out["steady_s_per_iter"] = round(
                self.steady_s / self.steady_iters, 4)
        if self.guard_report:
            out["host_syncs"] = self.guard_report.get("host_syncs")
            out["post_warmup_cache_misses"] = self.guard_report.get(
                "post_warmup_cache_misses")
        self._publish(out)
        return out

    def _publish(self, d: Dict) -> None:
        """Mirror this phase into the registry (gauges keyed by phase name —
        idempotent, so repeated to_dict() calls don't skew anything)."""
        from . import get_registry
        reg = get_registry()
        for key in ("compile_s", "steady_s", "steady_iters",
                    "steady_s_per_iter"):
            if d.get(key) is not None:
                reg.gauge(f"phase.{self.name}.{key}").set(d[key])
