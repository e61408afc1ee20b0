"""Optional ``jax.profiler`` capture window over a boosting-iteration range.

``tpu_profile_iters=start:stop`` captures a device-level profile (XProf /
TensorBoard / Perfetto) of exactly the iterations ``[start, stop)`` instead
of the whole run (``tpu_profile_dir`` alone wraps the full training loop in
one trace — ``maybe_xla_trace`` below). The window is the deep-profiling
leg of the telemetry contract: host-side spans (tracer.py) attribute
dispatch boundaries; the profiler window attributes the device program
(histogram / split / partition) for the chosen iterations only, keeping
profile volume bounded at bench scale.

Window edges land on DISPATCH boundaries: under ``tree_batch=K`` the trace
starts at the first batch whose iterations overlap the window and stops at
the first batch boundary at-or-past ``stop`` — a fused batch is never split
(that would change the compiled program, violating the zero-recompile
contract).

jax is imported lazily at the start edge so this module stays importable in
jax-free environments (the lint CLI imports the observability package).
"""
from __future__ import annotations

import contextlib
from typing import Optional, Tuple

from ..utils.log import Log


@contextlib.contextmanager
def maybe_xla_trace(profile_dir: str):
    """jax.profiler trace wrapper — the deep-profiling hook (XProf), gated
    on a non-empty directory (config tpu_profile_dir)."""
    if not profile_dir:
        yield
        return
    import jax
    with jax.profiler.trace(profile_dir):
        yield


def parse_profile_iters(spec: str) -> Optional[Tuple[int, int]]:
    """``"start:stop"`` -> (start, stop); None for empty. Raises ValueError
    on malformed input (config validation surfaces it as Log.fatal)."""
    if not spec:
        return None
    parts = spec.split(":")
    if len(parts) != 2:
        raise ValueError(
            f"tpu_profile_iters must be 'start:stop', got {spec!r}")
    try:
        start, stop = int(parts[0]), int(parts[1])
    except ValueError:
        raise ValueError(
            f"tpu_profile_iters must be two integers 'start:stop', "
            f"got {spec!r}") from None
    if start < 0 or stop <= start:
        raise ValueError(
            f"tpu_profile_iters needs 0 <= start < stop, got {spec!r}")
    return start, stop


class ProfileWindow:
    """Start/stop a ``jax.profiler`` trace when the training loop crosses
    the configured iteration window (engine.train calls ``before_step`` /
    ``after_step`` at batch boundaries and ``close`` on exit)."""

    def __init__(self, spec: str, out_dir: str):
        window = parse_profile_iters(spec)
        if window and not out_dir:
            Log.warning("tpu_profile_iters=%s has no output directory "
                        "(set tpu_profile_dir or telemetry_dir) — "
                        "profiling window disabled", spec)
            window = None
        self.start_iter, self.stop_iter = window or (0, 0)
        self.enabled = window is not None
        self.out_dir = out_dir
        self.active = False
        self._done = False

    def before_step(self, it: int, batch: int = 1) -> None:
        """Called with the first iteration of the batch about to dispatch
        and the batch's iteration count. The trace starts at the first
        batch that OVERLAPS the window ([it, it+batch) ∩ [start, stop) is
        non-empty) — a window that begins mid-batch, or sits entirely
        inside one fused batch, still captures that batch instead of being
        clipped or silently skipped."""
        if not self.enabled or self.active or self._done:
            return
        if it >= self.stop_iter:        # resumed run already past the window
            self._done = True
            return
        if it + max(batch, 1) > self.start_iter:
            import jax
            jax.profiler.start_trace(self.out_dir)
            self.active = True
            Log.info("tpu_profile_iters: jax.profiler trace started at "
                     "iteration %d (window %d:%d) -> %s", it,
                     self.start_iter, self.stop_iter, self.out_dir)
            from . import get_tracer
            get_tracer().event("profiler_window_start", iteration=it,
                               out_dir=self.out_dir)

    def after_step(self, it_end: int) -> None:
        """Called with the first iteration AFTER the batch that finished."""
        if self.active and it_end >= self.stop_iter:
            self._stop(it_end)

    def close(self) -> None:
        """Stop an in-flight trace at training exit (early stop, errors)."""
        if self.active:
            self._stop(-1)

    def _stop(self, it_end: int) -> None:
        import jax
        try:
            jax.profiler.stop_trace()
        finally:
            self.active = False
            self._done = True
        Log.info("tpu_profile_iters: jax.profiler trace stopped (%s) -> %s",
                 f"iteration {it_end}" if it_end >= 0 else "training exit",
                 self.out_dir)
        from . import get_tracer
        get_tracer().event("profiler_window_stop", iteration=it_end,
                           out_dir=self.out_dir)
