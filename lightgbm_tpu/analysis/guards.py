"""Runtime recompile/transfer guard — the enforced twin of tpu-lint.

The static rules catch recompile *hazards*; this context manager catches
recompiles that actually happened. A steady-state GBDT training loop must
dispatch the SAME compiled executable every iteration: the iteration
counter travels as a device array, the shrinkage scalar is cached
on-device, shapes are fixed. Any post-warm-up jit cache miss means a shape
or static-arg leak sneaked back in — at the headline shape one recompile
of the train step costs minutes, so it fails the run instead of
degrading it.

Cache misses are observed as per-entrypoint ``_cache_size()`` deltas on
the registered jitted callables (jax's pjit caches one executable per
distinct (shapes, statics) signature — the cache growing IS the miss).
Host syncs are counted by intercepting the ``jax.Array`` -> host
conversion surface (``__array__``/``item``/``tolist``/``__float__``/...)
for the duration of the context — the runtime analog of lint rule R002.
Caveat: on the CPU backend ``np.asarray`` converts zero-copy through the
buffer protocol and never reaches ``__array__``, so it is invisible here;
on a real TPU (where a sync actually costs something) every conversion
goes through the patched surface and is counted.

Usage (bench.py --smoke, tests/test_guards.py):

    guard = RecompileGuard()
    guard.register(booster._gbdt._step_fn, "train_step")
    with guard:
        guard.mark_warm()
        for _ in range(iters):
            booster.update()
    # raises GuardViolation on any post-warm-up recompile

jax is imported lazily so `lightgbm_tpu.analysis` (the lint CLI) stays
importable in jax-free environments.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict, Optional


class GuardViolation(RuntimeError):
    """A guarded invariant (no steady-state recompiles / no implicit host
    transfers) was broken."""


# jax.Array methods whose call implies a device->host sync
_SYNC_METHODS = ("__array__", "__float__", "__int__", "__bool__",
                 "__index__", "item", "tolist")


class RecompileGuard:
    """Counts jit cache misses per registered entrypoint and implicit
    host-sync events; optionally fails on either.

    Parameters
    ----------
    label: tag used in violation messages ("train", "smoke", ...).
    fail: raise GuardViolation on exit when post-warm-up misses > 0.
    disallow_transfers: raise at the call site on any implicit
        device->host sync inside the context (the strict mode used by
        tests that pin down the zero-sync property of the wave loop).
    """

    def __init__(self, label: str = "train", fail: bool = True,
                 disallow_transfers: bool = False):
        self.label = label
        self.fail = fail
        self.disallow_transfers = disallow_transfers
        self._entry: Dict[str, Callable] = {}
        self._warm_sizes: Optional[Dict[str, int]] = None
        self._start_sizes: Dict[str, int] = {}
        self._transfers = 0
        self._saved_methods = None
        self._sync_surface_ok = None     # None until the context is entered
        self._active = False

    # ------------------------------------------------------------- tracking

    def register(self, fn: Callable, name: str = None) -> None:
        """Track a jitted entrypoint (anything exposing ``_cache_size()``)."""
        if fn is None:
            return
        if not hasattr(fn, "_cache_size"):
            raise TypeError(
                f"RecompileGuard.register: {fn!r} has no _cache_size(); "
                f"pass the jax.jit-wrapped callable itself")
        key = name or getattr(fn, "__name__", f"entry{len(self._entry)}")
        self._entry[key] = fn
        self._start_sizes[key] = self._cache_size(fn)
        if self._warm_sizes is not None:
            self._warm_sizes[key] = self._cache_size(fn)

    @staticmethod
    def _cache_size(fn) -> int:
        try:
            return int(fn._cache_size())
        except Exception:
            return 0

    def mark_warm(self) -> None:
        """Snapshot the caches: compiles after this point are violations."""
        self._warm_sizes = {k: self._cache_size(f)
                            for k, f in self._entry.items()}

    def cache_misses_since_warm(self) -> Dict[str, int]:
        base = self._warm_sizes if self._warm_sizes is not None \
            else self._start_sizes
        return {k: self._cache_size(f) - base.get(k, 0)
                for k, f in self._entry.items()}

    @property
    def transfers(self) -> int:
        """Implicit device->host sync events observed inside the context."""
        return self._transfers

    def report(self) -> dict:
        misses = self.cache_misses_since_warm()
        return {"label": self.label,
                "post_warmup_cache_misses": sum(misses.values()),
                "misses_by_entrypoint": misses,
                "host_syncs": self._transfers,
                "transfer_counting": self._sync_surface_ok,
                "warm_marked": self._warm_sizes is not None}

    # ------------------------------------------------------ transfer counting

    def _patch_sync_surface(self):
        # ArrayImpl is private jax API; if a jax upgrade moves it, transfer
        # counting degrades to disabled instead of killing the guarded run
        # (record-only bench guards must survive). Strict transfer mode
        # can't silently not-enforce, so that still raises.
        try:
            from jax._src.array import ArrayImpl
        except ImportError as e:
            self._saved_methods = None
            self._sync_surface_ok = False
            if self.disallow_transfers:
                raise RuntimeError(
                    f"[{self.label}] disallow_transfers requested but the "
                    f"jax.Array sync surface cannot be patched: {e}") from e
            return
        self._sync_surface_ok = True
        guard = self
        saved = {}
        for mname in _SYNC_METHODS:
            orig = ArrayImpl.__dict__.get(mname)
            if orig is None:
                continue

            def make_wrapper(orig_fn, mname=mname):
                def wrapper(self_arr, *a, **kw):
                    guard._transfers += 1
                    if guard.disallow_transfers:
                        raise GuardViolation(
                            f"[{guard.label}] implicit device->host sync "
                            f"via jax.Array.{mname} inside a transfer-"
                            f"guarded region")
                    return orig_fn(self_arr, *a, **kw)
                return wrapper

            saved[mname] = orig
            setattr(ArrayImpl, mname, make_wrapper(orig))
        self._saved_methods = (ArrayImpl, saved)

    def _unpatch_sync_surface(self):
        if not self._saved_methods:
            return
        cls, saved = self._saved_methods
        for mname, orig in saved.items():
            setattr(cls, mname, orig)
        self._saved_methods = None

    # ------------------------------------------------------- context manager

    def __enter__(self) -> "RecompileGuard":
        self._active = True
        self._transfers = 0
        self._patch_sync_surface()
        return self

    def _publish_report(self) -> None:
        """Feed the guard's totals into the process-wide metrics registry
        (lightgbm_tpu/observability) — the single home of recompile /
        host-sync counters; bench.py and serving snapshots read them there.
        Best-effort: the guard must keep working if the registry cannot."""
        try:
            from ..observability import get_registry
        except Exception:                                    # noqa: BLE001
            return
        reg = get_registry()
        misses = sum(self.cache_misses_since_warm().values()) \
            if self._warm_sizes is not None else 0
        if misses > 0:
            reg.counter("recompiles.post_warmup").inc(misses)
        if self._transfers:
            reg.counter("host_syncs").inc(self._transfers)
        reg.counter("guard.windows").inc()

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._unpatch_sync_surface()
        self._active = False
        self._publish_report()
        if exc_type is not None:
            return False
        if self.fail and self._warm_sizes is not None:
            misses = self.cache_misses_since_warm()
            total = sum(misses.values())
            if total > 0:
                detail = ", ".join(f"{k}: +{v}" for k, v in misses.items()
                                   if v)
                raise GuardViolation(
                    f"[{self.label}] {total} jit cache miss(es) after "
                    f"warm-up ({detail}) — the steady-state loop "
                    f"recompiled; a shape, weak-type, or static-arg "
                    f"signature changed between iterations")
        return False


@contextlib.contextmanager
def recompile_guard(entrypoints=(), label: str = "train", fail: bool = True,
                    warm: bool = True, disallow_transfers: bool = False):
    """Functional wrapper: entrypoints pre-registered, warm-marked on entry.

        with recompile_guard([step_fn]) as g:
            for _ in range(n):
                step()
        assert g.transfers == 0
    """
    g = RecompileGuard(label=label, fail=fail,
                       disallow_transfers=disallow_transfers)
    for fn in entrypoints:
        g.register(fn)
    with g:
        if warm:
            g.mark_warm()
        yield g
