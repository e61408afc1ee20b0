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
- always-on records (this file)   — ``timed_span``: the same span, its
  seconds ALSO handed to the registry whatever the tracer's state. Set-up
  is tiled with it (``setup_span`` / ``setup_stages``: the gauges
  ``setup.*_s``, ``setup.unnamed_s``), every call of the step is recorded
  with it (``step_call`` / ``step_part``: the per-call summaries
  ``step.host_s`` and its parts, ``step.gap_s``, ``step.gc_s``), and a
  call that gained an executable is split with ``compile_watch``
  (``compile.step_first_call_s``, ``compile.step_trace_s`` ...).
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
event, at host boundaries only; the always-on records add eight
``perf_counter`` reads and six summary observations a call of the step
(microseconds beside a dispatch; docs/Observability.md). ``bench.py
--smoke`` enforces that telemetry-on adds zero steady-state recompiles and
zero new host syncs inside the fused step.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import os
import sys
import time
from typing import Callable, Dict, List, Optional

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
def timed_span(name: str, record: Callable[[float], None], **args):
    """``span(name)`` whose seconds are ALWAYS handed to ``record``: the
    tracer's state decides whether the span is kept, never whether the
    boundary is timed. The always-on records (set-up's gauges, the step's
    per-call summaries, ``eval.host_s``) are all this one helper with
    another ``record``. Yields its start on ``clock()``, so a caller that
    wants the seconds so far reads the clock once, not twice. Cost with
    tracing off: two ``perf_counter`` reads, the no-op span and the call
    of ``record``."""
    t0 = time.perf_counter()
    try:
        with span(name, **args):
            yield t0
    finally:
        record(time.perf_counter() - t0)


# ---- set-up, tiled by spans ------------------------------------------------

# the two wholes of the program's set-up before the first dispatch
# (basic.Dataset.construct, basic.Booster._setup_train); what their direct
# children do not name is the gauge ``setup.unnamed_s``
SETUP_WHOLES = ("dataset.construct", "booster.init")
# the set-up boundaries ``time_tag_summary`` prints, summed over the process
TIME_TAG_SETUP = ("dataset.construct", "dataset.construct_valid",
                  "booster.init", "finalize.fetch")
_setup_open: List[List[float]] = []     # open set-up spans: [children's s]
_setup_unnamed: Dict[str, float] = {}   # per whole: its own s - children's


def _setup_metric(name: str) -> str:
    return f"setup.{name.replace('.', '_')}_s"


@contextlib.contextmanager
def setup_span(name: str, **args):
    """``span(name)`` around a boundary of set-up (dataset construction,
    the booster's stages, ingest, the finalize fetch), whose seconds are
    ALWAYS written to the gauge ``setup.<name with dots as underscores>_s``
    (the last such boundary's seconds: what the benchmark reads, with the
    tracer off); the ``TIME_TAG_SETUP`` boundaries also keep a histogram of
    that name (count and sum over the process: what ``time_tag_summary``
    prints). Set-up runs once per dataset or booster, so timing it costs
    nothing that matters. A set-up span opened inside another is its child;
    when ``booster.init`` ends, ``setup.unnamed_s`` is set to the seconds
    of the two ``SETUP_WHOLES`` that no direct child of theirs names."""
    children = [0.0]
    record_as = _setup_metric(name)

    def record(seconds: float) -> None:
        _setup_open.pop()
        if _setup_open:
            _setup_open[-1][0] += seconds
        _registry.gauge(record_as).set(seconds)
        if name in TIME_TAG_SETUP:
            _registry.histogram(record_as).observe(seconds)
        if name in SETUP_WHOLES:
            _setup_unnamed[name] = max(seconds - children[0], 0.0)
            if name == "booster.init":
                _registry.gauge("setup.unnamed_s").set(
                    sum(_setup_unnamed.values()))

    _setup_open.append(children)
    with timed_span(name, record, **args):
        yield


@contextlib.contextmanager
def setup_stages():
    """Consecutive set-up spans with no gap between them, for a long
    constructor: ``stage(name)`` ends the stage that is open and starts the
    next; leaving the block ends the last (with the error, if one is on
    its way out)."""
    with contextlib.ExitStack() as open_stage:
        def stage(name: str, **args) -> None:
            open_stage.close()
            open_stage.enter_context(setup_span(name, **args))
        yield stage


# ---- the step, call by call ------------------------------------------------

STEP_SERIES = ("step.host_s", "step.host.prep_s", "step.host.launch_s",
               "step.host.post_s", "step.gap_s", "step.gc_s")
_STEP_PARTS = {"prep": "step.prep", "launch": "step.dispatch",
               "post": "step.post"}


class _StepCalls:
    """Process-wide state of the per-call record (``step_call``)."""

    def __init__(self):
        self.last_return: Optional[float] = None
        self.parts: Optional[Dict[str, float]] = None   # of the open call
        # the collector, as its own callbacks time it: never the registry
        # from inside the hook (a collection can start under its lock)
        self.gc_hooked = False
        self.gc_t0 = 0.0
        self.gc_seconds = 0.0
        self.gc_seen = 0.0          # ``gc_seconds`` at the last entry

    def gc_hook(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self.gc_t0 = time.perf_counter()
        else:
            self.gc_seconds += time.perf_counter() - self.gc_t0

    def gc_since_last_entry(self) -> float:
        """Seconds the collector ran since the previous call's entry."""
        if not self.gc_hooked:
            # the first dispatch of the process: set-up never pays for it
            gc.callbacks.append(self.gc_hook)
            self.gc_hooked = True
        seen, self.gc_seen = self.gc_seen, self.gc_seconds
        return self.gc_seen - seen

    def unhook(self) -> None:
        if self.gc_hooked:
            gc.callbacks.remove(self.gc_hook)


_steps = _StepCalls()


@contextlib.contextmanager
def step_call():
    """One call of ``train_one_iter`` / ``train_batch`` / the custom-fobj
    step, ALWAYS recorded: one observation in each of ``STEP_SERIES``, in
    call order (windowed summaries, read back call by call with
    ``Summary.values()``). ``step.host_s`` is entry to return;
    ``step.host.{prep,launch,post}_s`` its parts (``step_part``);
    ``step.gap_s`` the previous call's return to this entry (what the
    caller did in between: its block on the device, its evaluation);
    ``step.gc_s`` the seconds the Python collector ran from the previous
    call's entry to this one. A dispatch's length from inside is
    ``step.host_s[i] + step.gap_s[i + 1]``, its collector seconds
    ``step.gc_s[i + 1]``. No device value is read and nothing is blocked
    on."""
    t0 = time.perf_counter()
    gc_s = _steps.gc_since_last_entry()
    gap_s = 0.0 if _steps.last_return is None else t0 - _steps.last_return
    parts = _steps.parts = {"prep": 0.0, "launch": 0.0, "post": 0.0}
    try:
        yield
    finally:
        _steps.parts = None
        _steps.last_return = t1 = time.perf_counter()
        for name, v in zip(STEP_SERIES, (t1 - t0, parts["prep"],
                                         parts["launch"], parts["post"],
                                         gap_s, gc_s)):
            _registry.summary(name).observe(v)


def _add_step_part(part: str, seconds: float) -> None:
    if _steps.parts is not None:
        _steps.parts[part] += seconds


def step_part(part: str, **args):
    """The span ``step.prep`` / ``step.dispatch`` / ``step.post`` of the
    open ``step_call``, its seconds added to that call's ``part``
    (``prep`` / ``launch`` / ``post``) whether or not the tracer is on."""
    return timed_span(_STEP_PARTS[part],
                      functools.partial(_add_step_part, part), **args)


# ---- a call that compiled, split -------------------------------------------

COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_compile_watch: Dict = {"listening": False, "fired": None}


def _on_compile_event(event: str, duration: float, **_kw) -> None:
    fired = _compile_watch["fired"]
    if fired is not None and event in COMPILE_EVENTS:
        end = time.perf_counter()
        fired.append((COMPILE_EVENTS[event], end - duration, end))


@contextlib.contextmanager
def compile_watch():
    """Yields a list that gains ``(kind, start, end)`` for every
    ``jax.monitoring`` duration of ``COMPILE_EVENTS`` that fires inside the
    block (the listener is registered once, at the first use). They fire
    only when something is traced, lowered, compiled or loaded: a steady
    dispatch appends nothing."""
    if not _compile_watch["listening"]:
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_event)
        _compile_watch["listening"] = True
    outer = _compile_watch["fired"]
    fired = _compile_watch["fired"] = []
    try:
        yield fired
    finally:
        _compile_watch["fired"] = outer


def compile_split(fired) -> Dict[str, float]:
    """Seconds by kind of what ``compile_watch`` saw, each kind the UNION
    of its intervals: jax times an inner jit's trace inside its caller's,
    and a sum would count those seconds twice. ``backend`` is compile OR
    load (jax times both under one name); ``cache_load`` is the retrieval
    alone, inside it."""
    out = {kind: 0.0 for kind in COMPILE_EVENTS.values()}
    for kind in out:
        covered_to = float("-inf")
        for start, end in sorted(iv[1:] for iv in fired if iv[0] == kind):
            if end > covered_to:
                out[kind] += end - max(start, covered_to)
                covered_to = end
    return out


def time_tag_summary() -> str:
    """The operator's end-of-training phase summary (``tpu_time_tag`` /
    ``LGBM_TPU_TIMETAG``; the reference prints its TIMETAG accumulators at
    destruction), read from the registry's always-on records: no timer of
    its own. Every row is a boundary's sum over the process and its count
    (a training set and three valid sets: ``setup.dataset_construct_s x1``,
    ``setup.dataset_construct_valid_s x3``)."""
    snap = _registry.snapshot()
    rows = []
    for kind, names in (
            ("summaries", ("step.host_s", "eval.host_s")),
            ("histograms", map(_setup_metric, TIME_TAG_SETUP))):
        for name in names:
            rec = snap.get(kind, {}).get(name)
            if rec and rec["count"]:
                # a histogram's snapshot carries its sum, a summary's not
                rows.append((name, rec.get("sum", rec["mean"] * rec["count"]),
                             rec["count"]))
    if not rows:
        return "TIMETAG: (no phases recorded)"
    width = max(len(name) for name, _, _ in rows)
    return "\n".join(
        ["TIMETAG phase summary (host seconds):"]
        + [f"  {name:<{width}}  {seconds:9.3f}s  x{count}"
           for name, seconds, count in sorted(rows, key=lambda r: -r[1])])


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
    _setup_open.clear()
    _setup_unnamed.clear()
    global _steps
    _steps.unhook()
    _steps = _StepCalls()
    _costs.reset_for_tests()
