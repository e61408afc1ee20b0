"""Host-side span tracer: nested wall-clock spans at dispatch boundaries.

The span tree mirrors the training stack's host-visible structure; every
span is a MEASUREMENT of a host boundary that exists (nothing is sliced or
modelled):

    train                       engine.train (one per call)
      tree_batch                one jit dispatch (K fused iterations)
        iteration               tree_batch=1 only: one iteration, one dispatch
          step.prep             argument assembly (_dispatch_prep)
          step.dispatch         the jitted call: enqueue, or trace + compile
                                + cache load on a first call
          step.post             bookkeeping, nan policy
      eval | comm | checkpoint  real host-side operations
    dataset.construct > dataset.* | booster.init > booster.* > ingest >
    ingest.compile | finalize.fetch          set-up, tiled
                                             (docs/Observability.md)

Some of these boundaries are timed ALWAYS, tracer on or off
(``observability.timed_span``: set-up's, the step's three, ``eval``): the
tracer decides only whether the span is kept.

What happens INSIDE a dispatch has no host boundary: the wave loop runs on
the device. Its phases are ``jax.named_scope`` names in the compiled
program (``wave.*``, ``step.*``, ``hist.kernel``; docs/Observability.md)
that a ``jax.profiler`` trace carries per device operation, and its work
is counted by the loop itself (grower.WaveStats), fetched with the trees.

One clock: ``observability.span`` also enters a
``jax.profiler.TraceAnnotation("lgbm.<name>")`` whenever a profiler
session is open, so the same spans sit in the profiler's host plane beside
the device trace. Each recorded span carries ``span_id`` and the
``parent_id`` of the span that was open on its thread when it started.

Spans are recorded ONLY at host dispatch boundaries: entering/leaving a span
costs two ``time.perf_counter()`` calls and one dict append — no device
array is ever touched, so the fused ``tree_batch`` path stays recompile-free
and host-sync-free with telemetry on (asserted by ``bench.py --smoke``).

When disabled (the default), ``span()`` returns a shared no-op context
manager: the hot loop pays one attribute check per dispatch and nothing
else.

Events use the Chrome trace-event schema directly (``ph: "X"`` complete
events, microsecond timestamps) so the JSONL stream and the Perfetto
export are the same records (export.py).
"""
from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional


class _NullSpan:
    """Shared no-op context manager for the disabled tracer."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("_tracer", "name", "args", "_t0", "_annotation", "span_id",
                 "parent_id")

    def __init__(self, tracer: "SpanTracer", name: str, args: Dict,
                 annotation=None):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._annotation = annotation

    def __enter__(self):
        self.span_id, self.parent_id = self._tracer._push()
        if self._annotation is not None:
            self._annotation.__enter__()
        self._t0 = self._tracer._now_us()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._tracer._finish(self, self._t0, exc_type)
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        return False


class SpanTracer:
    """Bounded in-memory recorder of finished spans and instant events."""

    def __init__(self, max_events: int = 200_000):
        self.enabled = False
        self.max_events = max_events
        self.dropped = 0
        self._events: List[Dict] = []
        self._lock = threading.Lock()
        self._open = threading.local()      # per-thread stack of open ids
        self._next_id = 0
        self._epoch = time.perf_counter()
        self._epoch_unix = time.time()

    # --------------------------------------------------------------- recording

    def _now_us(self) -> int:
        return int((time.perf_counter() - self._epoch) * 1e6)

    def span(self, name: str, _annotation=None, **args):
        """Context manager recording one complete ("X") span on exit.
        ``_annotation`` (observability.span: an un-entered profiler
        annotation) is entered and left with the span."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, args, _annotation)

    def _push(self):
        """(new span id, id of the span open on this thread or None)."""
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        with self._lock:
            self._next_id += 1
            sid = self._next_id
        parent = stack[-1] if stack else None
        stack.append(sid)
        return sid, parent

    def _finish(self, span: _Span, t0: int, exc_type) -> None:
        self._open.stack.pop()
        args = span.args
        if exc_type is not None:
            args = dict(args, error=exc_type.__name__)
        self._record({"name": span.name, "ph": "X", "ts": t0,
                      "dur": max(self._now_us() - t0, 0),
                      "pid": os.getpid(), "tid": threading.get_ident(),
                      "cat": "lightgbm_tpu", "args": args,
                      "span_id": span.span_id, "parent_id": span.parent_id})

    def event(self, name: str, **args) -> None:
        """Instant ("i") event — e.g. a nan_policy trip, a booster init."""
        if not self.enabled:
            return
        self._record({"name": name, "ph": "i", "ts": self._now_us(), "s": "p",
                      "pid": os.getpid(), "tid": threading.get_ident(),
                      "cat": "lightgbm_tpu", "args": args})

    def _record(self, ev: Dict) -> None:
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            self._events.append(ev)

    # ----------------------------------------------------------------- export

    def events(self) -> List[Dict]:
        """Copy of every recorded event (chronological by record order)."""
        with self._lock:
            return list(self._events)

    def events_since(self, cursor: int):
        """(new_events, new_cursor) — incremental drain for the JSONL sink."""
        with self._lock:
            return list(self._events[cursor:]), len(self._events)

    def epoch_unix(self) -> float:
        """Wall-clock time of ``ts == 0`` (for correlating JSONL streams)."""
        return self._epoch_unix

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0
            self._next_id = 0
            self._epoch = time.perf_counter()
            self._epoch_unix = time.time()
