"""Unified training telemetry (docs/Observability.md).

One subsystem for every runtime signal the boosting stack produces:

- ``SpanTracer`` (tracer.py)      — nested host-side spans
  (train -> tree_batch -> iteration -> step.prep/dispatch/post, plus
  eval/comm/checkpoint and the set-up boundaries), recorded at dispatch
  boundaries only so the fused step and the recompile-free steady state
  are preserved. ``span()`` also enters a ``jax.profiler.TraceAnnotation``
  named ``lgbm.<name>`` while a profiler session is open: the program's
  spans then sit on the device trace's own clock. "Tracing on" IS "a
  profiler session is open" — there is no other switch.
- ``MetricsRegistry`` (metrics.py) — process-wide counters/gauges/
  histograms/quantile summaries absorbing ``RecompileGuard.report()``,
  ``PhaseBreakdown``, comm retries/timeouts, ``nan_policy`` events,
  checkpoint writes, per-booster kernel choice, the wave loop's own
  per-tree counters (``grow.*``, ``rows.routed``, ``hist.mxu_flops``),
  the retrace counter ``compile.step_traces``, set-up seconds
  (``setup.*_s``), and the serving subsystem's per-request latency
  p50/p99 (``serve.*``, docs/Serving.md).
- exporters (export.py)           — JSONL event stream + Chrome trace-event
  JSON (Perfetto-loadable) under ``LGBM_TPU_TELEMETRY_DIR`` / config
  ``telemetry_dir``; ``snapshot()`` is the point-in-time serving API.
- ``ProfileWindow`` (profiler.py) — optional ``jax.profiler`` capture of an
  iteration range (``tpu_profile_iters=start:stop``).
- cost reports (costs.py)         — compile-time ``cost_analysis()`` /
  ``memory_analysis()`` capture per dispatch site (opt-in:
  ``tpu_cost_analysis`` / ``LGBM_TPU_COST_ANALYSIS``), published as
  ``cost.<site>.*`` gauges, into ``snapshot()``, and as Perfetto metadata.
- HBM accounting (memory.py)      — ``device_memory()`` stats helper and
  the analytic pre-flight residency estimate ``engine.train`` budgets
  against before the first compile.
- perf ledger (ledger.py)         — normalized BENCH/MULTICHIP history +
  regression compare (``bench.py --compare`` / ``make bench-diff``).

The module singletons are process-wide on purpose: a training run, the
bench harness, and a serving probe all read the same registry. Everything
here is jax-free at import time (the lint CLI and guards publish through
it in jax-free environments).

Overhead contract: with no telemetry directory configured and no profiler
session open, ``span()`` returns a shared no-op (one ``sys.modules`` lookup
and one flag check) and the registry costs one dict lookup + int add per
event, at host boundaries only. ``bench.py
--smoke`` enforces that telemetry-on adds zero steady-state recompiles and
zero new host syncs inside the fused step.
"""
from __future__ import annotations

import contextlib
import os
import sys
import time
from typing import Dict, Optional

from .metrics import MetricsRegistry
from .phases import PhaseBreakdown  # noqa: F401  (public: bench phase timing)
from .tracer import SpanTracer

ENV_TELEMETRY_DIR = "LGBM_TPU_TELEMETRY_DIR"


def clock() -> float:
    """Monotonic wall-clock for package modules whose measurements FEED the
    registry/trace (the streaming prefetcher's stall accounting,
    ops/stream.py). tpu-lint R008 keeps raw ``time.perf_counter()`` out of
    package code so no timing lives outside observability; this is the one
    sanctioned source for code that reports its numbers here."""
    return time.perf_counter()

_registry = MetricsRegistry()
_tracer = SpanTracer()
_state: Dict = {"dir": None, "jsonl_cursor": 0, "env_checked": False}


# ------------------------------------------------------------- configuration

def get_registry() -> MetricsRegistry:
    return _registry


def get_tracer() -> SpanTracer:
    return _tracer


def enabled() -> bool:
    """True when spans are being recorded (a telemetry dir is configured or
    the tracer was force-enabled)."""
    return _tracer.enabled


def telemetry_dir() -> Optional[str]:
    return _state["dir"]


def configure(telemetry_dir: Optional[str] = None,
              enabled: Optional[bool] = None) -> None:
    """Point the exporters at ``telemetry_dir`` (created if missing) and/or
    force the tracer on/off. Setting a directory enables the tracer unless
    ``enabled=False`` is passed explicitly."""
    if telemetry_dir:
        os.makedirs(telemetry_dir, exist_ok=True)
        _state["dir"] = telemetry_dir
        if enabled is None:
            enabled = True
    if enabled is not None:
        _tracer.enabled = bool(enabled)


def maybe_configure_from_env() -> None:
    """Honor ``LGBM_TPU_TELEMETRY_DIR`` once per process (called from every
    training entry point; explicit ``configure()`` calls always win)."""
    if _state["env_checked"]:
        return
    _state["env_checked"] = True
    env = os.environ.get(ENV_TELEMETRY_DIR)
    if env and _state["dir"] is None:
        configure(telemetry_dir=env)


# ----------------------------------------------------------------- recording

PROFILER_PREFIX = "lgbm."


def _live_annotation():
    """``jax.profiler.TraceAnnotation`` while a profiler session is open,
    else None. jax is looked up in ``sys.modules``, never imported: a
    process that has not imported it has no session, and this package
    stays importable without it. ``is_enabled`` is the profiler's own
    flag check."""
    prof = sys.modules.get("jax.profiler")
    if prof is None:
        return None
    ann = prof.TraceAnnotation
    return ann if ann.is_enabled() else None


def span(name: str, **args):
    """``with observability.span("tree_batch", k=4): ...``. Recorded in
    memory when the tracer is on; entered as the profiler annotation
    ``lgbm.<name>`` when a profiler session is open (the same span on the
    device trace's clock); the shared no-op when neither."""
    ann = _live_annotation()
    if ann is None:
        return _tracer.span(name, **args)
    live = ann(PROFILER_PREFIX + name, **args)
    return _tracer.span(name, _annotation=live, **args) \
        if _tracer.enabled else live


@contextlib.contextmanager
def setup_span(name: str, **args):
    """``span(name)`` around a boundary of set-up (dataset construction,
    ingest, the finalize fetch), whose seconds are also ALWAYS written to
    the gauge ``setup.<name with dots as underscores>_s``: set-up runs once
    per dataset or booster, so timing it costs nothing that matters, and
    the benchmark reads these with the tracer off."""
    t0 = time.perf_counter()
    try:
        with span(name, **args):
            yield
    finally:
        _registry.gauge(f"setup.{name.replace('.', '_')}_s").set(
            time.perf_counter() - t0)


def event(name: str, **args) -> None:
    _tracer.event(name, **args)


def inc(name: str, n: int = 1) -> None:
    _registry.inc(name, n)


# ------------------------------------------------------------------- export

def trace_path() -> Optional[str]:
    d = _state["dir"]
    return os.path.join(d, f"trace_{os.getpid()}.json") if d else None


def jsonl_path() -> Optional[str]:
    d = _state["dir"]
    return os.path.join(d, f"events_{os.getpid()}.jsonl") if d else None


def snapshot() -> Dict:
    """Point-in-time metrics snapshot (the serving API): registry contents
    plus tracer bookkeeping, the captured compile-time cost reports
    (costs.py), and the device memory stats (memory.py — ``{}``-safe in a
    jax-free process, so this stays callable from anywhere)."""
    snap = _registry.snapshot()
    snap["spans_recorded"] = len(_tracer.events())
    snap["spans_dropped"] = _tracer.dropped
    from . import costs as _costs
    cost_reports = _costs.reports()
    if cost_reports:
        snap["cost_reports"] = cost_reports
    from .memory import device_memory
    dm = device_memory()
    if dm:
        snap["device_memory"] = dm
    return snap


def write_snapshot(path: str) -> str:
    """Write ``snapshot()`` to ``path`` as JSON (atomic) — the
    ``--dump-snapshot`` / train-end artifact a chip run brings back."""
    from .export import atomic_write_json
    return atomic_write_json(path, snapshot(), indent=1, sort_keys=True,
                             trailing_newline=True)


def flush() -> Optional[str]:
    """Write pending telemetry to disk: append new events + a counters
    record to the JSONL stream, rewrite the Chrome trace. Returns the trace
    path (None when no directory is configured). Called at training exit
    (engine.train) and bench boundaries — never inside the hot loop."""
    d = _state["dir"]
    if not d:
        return None
    from .export import JsonlWriter, write_chrome_trace
    new, _state["jsonl_cursor"] = _tracer.events_since(_state["jsonl_cursor"])
    records = [dict(ev, type="span" if ev.get("ph") == "X" else "event")
               for ev in new]
    records.append(dict(snapshot(), type="counters"))
    JsonlWriter(jsonl_path()).append(records)
    from . import costs as _costs
    metadata = {"epoch_unix": _tracer.epoch_unix()}
    cost_reports = _costs.reports()
    if cost_reports:
        # compile-time cost reports ride as trace metadata so the Perfetto
        # artifact is self-describing about what the traced step costs
        metadata["cost_reports"] = cost_reports
    return write_chrome_trace(_tracer.events(), trace_path(),
                              metadata=metadata)


def reset_for_tests() -> None:
    """Full reset of the process-wide singletons (test isolation)."""
    from . import costs as _costs
    _registry.reset()
    _tracer.reset()
    _tracer.enabled = False
    _state["dir"] = None
    _state["jsonl_cursor"] = 0
    _state["env_checked"] = False
    _costs.reset_for_tests()
