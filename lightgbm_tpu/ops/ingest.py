"""Device-side dataset ingest: on-device binning, in-trace code packing,
double-buffered H2D chunk feeding (``tpu_ingest=device|auto``).

Host dataset construction binned every column serially through
``BinMapper.value_to_bin`` (binning.py) and materialized the full
``X_binned`` matrix before a single tree trained — at the 10.5M-row HIGGS
scale that is a fixed multi-second tax invisible to every training bench.
This module moves the bin application onto the accelerator, following the
quantile-sketch + feature-packing design of "XGBoost: Scalable GPU
Accelerated Learning" (arXiv 1806.11248) and the overlapped out-of-core
ingest discipline of "Out-of-Core GPU Gradient Boosting" (arXiv
2005.09148): raw f32 row chunks stream H2D under the previous chunk's
bin+pack compute, and the packed code layout lands directly in the device
residency buffers — host ``X_binned`` is never built.

Bit-exactness contract (pinned in tests/test_ingest.py): the device path
reproduces ``BinMapper.value_to_bin`` EXACTLY, not approximately.

- Numerical. The host oracle computes, over f64 bounds ``ub``,
  ``bin = searchsorted(ub[:r+1], v, side="left")`` capped at ``r``
  (``r = num_bin-1``, minus one more under MISSING_NAN), i.e.
  ``bin = sum_k [ub_k < v]`` over the first ``r`` bounds (the cap is
  redundant: the trailing bound never compares below a finite value).
  The device works in f32 (R003: no f64 on device) over per-feature
  threshold rows ``t_k`` = the LARGEST f32 <= ``ub_k`` (round-to-nearest
  then a conditional ``nextafter`` step down). For any f32 value ``v``:
  ``t_k < v  =>  v >= nextafter(t_k, +inf) > ub_k``  and
  ``ub_k < v  =>  t_k <= ub_k < v`` — so ``[t_k < v] == [ub_k < v]``
  exactly, and ``bin = sum_k [v > t_k]`` matches the host bin for every
  f32 input, including ±inf, -0.0 and exact-tie values. The kernel
  computes that count as the plain compare-sum itself: one pass of
  ``pos += t_k < v`` per threshold row, ``O(B)`` per value, every step
  elementwise over the chunk and nothing gathered. An ``O(log B)``
  search gathers a pivot a step, and a gathered scalar costs 7 ns on a
  v5e where a compare-add costs a thousandth of that: 0.461 against
  0.0046 s for a 4,096 x 2,000 chunk at 255 bins (PERF.md, PR 30).
  Threshold rows are padded with +inf, which never counts (strict
  ``<``). NaN searches as 0.0 (the host's ``search_vals``) and is
  redirected to the last bin only under ``has_nan_bin``. Inputs must be
  losslessly f32-representable — :func:`device_ingest_blocker` gates
  engagement on exactly that.
- Categorical. The host truncates to int64 and dict-maps, negatives and
  unseen categories to the last bin. The device clamps to
  ``[-1, max_cat+1]`` BEFORE the f32->i32 truncating cast (same
  round-toward-zero as numpy ``astype``; the clamp keeps huge raw values
  out of int overflow — anything above the largest seen category clamps
  to an unseen value), then one-hot matches against a padded per-feature
  category table. Engagement requires every category < 2^24 (f32-exact
  integers) and a bounded per-feature category count.

Padding contract: the residency layout pads rows AND feature columns with
literal zero codes (``np.pad`` in boosting/gbdt.py), NOT with the default
bin — the jitted kernel masks rows past ``n_rows`` to 0 (the row offset is
a traced scalar, so every chunk shares ONE compiled executable per shape
class — RecompileGuard-pinned) and padded feature columns carry all-+inf
threshold rows, which bin every value to 0.

Overlap: :class:`ChunkFeeder` is the raw-chunk twin of
``ops/stream.ShardPrefetcher`` — same stall accounting (a ``get`` that
finds nothing prefetched is a counted, timed stall), same honesty knob
(``LGBM_TPU_INGEST_NO_PREFETCH=1`` forces every transfer into a measured
stall — ``bench.py --ingest``'s overlap-vs-no-overlap arm). Metrics:
``ingest.rows``, ``ingest.chunks``, ``ingest.bytes_h2d``,
``ingest.prefetch_hits``, ``ingest.stalls``, ``ingest.stall_seconds``
(histogram), under an ``ingest`` span (docs/Observability.md).

Module-level imports stay numpy-only: the eligibility helpers run inside
``dataset.construct_dataset`` before jax is ever needed; jax loads lazily
when a kernel is actually built.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..binning import BIN_CATEGORICAL, BIN_NUMERICAL, MISSING_NAN, BinMapper
from ..utils.log import Log

# f32 represents every integer in [-2^24, 2^24] exactly — categories at or
# beyond this would alias under the f32 raw-value transport
_CAT_EXACT_LIMIT = 1 << 24
# one-hot category matching is O(rows * categories) per feature; past this
# width the host dict map is the better tool
_CAT_TABLE_LIMIT = 1024
# auto-sized chunks target ~4 MiB of raw f32 per H2D transfer: big enough
# to amortize per-chunk dispatch, small enough that several chunks overlap
_CHUNK_BUDGET_BYTES = 4 << 20
_CHUNK_MIN, _CHUNK_MAX = 4096, 131072


# ------------------------------------------------------------- eligibility

def f32_lossless(data: np.ndarray, probe_stride: int = 257) -> bool:
    """True when every value survives the f64 -> f32 -> f64 round trip
    (NaN == NaN). The host oracle reads values through f64
    (``value_to_bin``'s ``asarray(..., float64)``), so f64 is the fidelity
    reference; f32 input is lossless by definition. A strided probe
    rejects most non-representable matrices without paying the full
    two-pass check."""
    if data.dtype == np.float32:
        return True
    if data.dtype != np.float64:
        return False

    def _roundtrips(x: np.ndarray) -> bool:
        return bool(np.array_equal(x.astype(np.float32).astype(np.float64),
                                   x, equal_nan=True))

    if data.shape[0] > probe_stride and not _roundtrips(data[::probe_stride]):
        return False
    return _roundtrips(data)


def device_ingest_blocker(data, mappers: Sequence[BinMapper]) -> Optional[str]:
    """Why device ingest cannot serve this input, or None when it can.
    Numpy-only: runs inside dataset construction before jax is touched."""
    if hasattr(data, "tocsc"):
        return "sparse input (device ingest bins dense raw rows)"
    if data.dtype not in (np.float32, np.float64):
        return (f"raw dtype {data.dtype} (device ingest transports raw "
                f"values as f32; pass float32/float64)")
    for m in mappers:
        if m.bin_type != BIN_CATEGORICAL:
            continue
        cats = [c for c in m.categorical_2_bin if c >= 0]
        if len(cats) > _CAT_TABLE_LIMIT:
            return (f"categorical feature with {len(cats)} categories "
                    f"(> {_CAT_TABLE_LIMIT}: one-hot table match would "
                    f"dominate the bin kernel)")
        if cats and max(cats) >= _CAT_EXACT_LIMIT:
            return (f"categorical value {max(cats)} >= 2^24 "
                    f"(not exactly representable in f32)")
    if data.dtype == np.float64:      # float32 is lossless by definition
        from .. import observability as obs
        with obs.setup_span("dataset.lossless_check"):
            lossless = f32_lossless(data)
        if not lossless:
            return ("float64 values not losslessly f32-representable "
                    "(device binning compares in f32)")
    return None


# ------------------------------------------------------------- bin tables

@dataclass
class IngestTables:
    """Host-built per-feature tables the jitted bin kernel closes over.
    All rows are padded to common widths; padded FEATURE columns get
    all-+inf thresholds (every value bins to 0 — the residency layout's
    zero column padding)."""
    thresholds: np.ndarray   # [C, T] f32; t_k = largest f32 <= ub_k
    nan_bin: np.ndarray      # [C] i32; num_bin-1 under has_nan_bin else -1
    is_cat: np.ndarray       # [C] bool
    cat_vals: np.ndarray     # [C, K] i32 category values (pad -2: never hit)
    cat_bins: np.ndarray     # [C, K] i32 bin of each category
    cat_last: np.ndarray     # [C] i32 last bin (negative/unseen categories)
    cat_hi: np.ndarray       # [C] f32 clamp ceiling (max category + 1)

    @property
    def has_categorical(self) -> bool:
        return bool(self.is_cat.any())


def f32_floor_thresholds(ub: np.ndarray) -> np.ndarray:
    """Largest f32 <= each f64 bound: round to nearest, then step down one
    ulp wherever rounding went UP (this is what makes the f32 compare-sum
    agree with the f64 searchsorted — module docstring proof)."""
    t = np.asarray(ub, np.float64).astype(np.float32)
    over = t.astype(np.float64) > ub
    if over.any():
        t[over] = np.nextafter(t[over], np.float32(-np.inf))
    return t


def build_ingest_tables(mappers: Sequence[BinMapper],
                        num_cols: int) -> IngestTables:
    """Pack every mapper's boundaries/categories into fixed-width arrays
    covering ``num_cols`` feature columns (>= len(mappers); the excess is
    residency column padding)."""
    C = max(int(num_cols), 1)
    th_rows: List[np.ndarray] = []
    cat_rows: List[Tuple[np.ndarray, np.ndarray]] = []
    nan_bin = np.full(C, -1, np.int32)
    is_cat = np.zeros(C, bool)
    cat_last = np.zeros(C, np.int32)
    cat_hi = np.zeros(C, np.float32)
    for j, m in enumerate(mappers):
        if m.bin_type == BIN_NUMERICAL:
            r = m.num_bin - 1 - (1 if m.missing_type == MISSING_NAN else 0)
            # the host search range is ub[:r+1], whose LAST bound (+inf, or
            # the NaN sentinel) never compares below a value — the first r
            # bounds are the whole decision surface
            th_rows.append(f32_floor_thresholds(m.bin_upper_bound[:r]))
            cat_rows.append((np.zeros(0, np.int32), np.zeros(0, np.int32)))
            if m.has_nan_bin:
                nan_bin[j] = m.num_bin - 1
        else:
            pairs = sorted((c, b) for c, b in m.categorical_2_bin.items()
                           if c >= 0)
            cat_rows.append((
                np.array([c for c, _ in pairs], np.int32),
                np.array([b for _, b in pairs], np.int32)))
            th_rows.append(np.zeros(0, np.float32))
            is_cat[j] = True
            cat_last[j] = m.num_bin - 1
            cat_hi[j] = np.float32((pairs[-1][0] + 1) if pairs else 0)
    T = max([len(r) for r in th_rows], default=0)
    K = max([len(v) for v, _ in cat_rows], default=0)
    # shorter rows are padded with +inf, which never compares below a
    # value, so the count of t_k < v is unchanged
    T, K = max(T, 1), max(K, 1)
    thresholds = np.full((C, T), np.inf, np.float32)
    cat_vals = np.full((C, K), -2, np.int32)
    cat_bins = np.zeros((C, K), np.int32)
    for j, row in enumerate(th_rows):
        thresholds[j, :len(row)] = row
    for j, (v, b) in enumerate(cat_rows):
        cat_vals[j, :len(v)] = v
        cat_bins[j, :len(v)] = b
    return IngestTables(thresholds, nan_bin, is_cat, cat_vals, cat_bins,
                        cat_last, cat_hi)


# ------------------------------------------------------------- bin kernel

class DeviceIngestor:
    """Jit-compiled bin(+pack) over fixed-shape raw chunks.

    One instance = one shape class: ``[chunk_rows, num_cols]`` f32 in,
    ``[chunk_rows, num_cols]`` codes (or the ``code_mode`` packed byte
    layout) out. The row offset is a TRACED scalar, so every chunk of a
    dataset — including the zero-masked tail — reuses the first chunk's
    executable (``compiles`` stays 1; RecompileGuard pin in
    tests/test_ingest.py)."""

    def __init__(self, mappers: Sequence[BinMapper], *, num_cols: int,
                 n_rows: int, out_dtype, code_mode: Optional[str] = None,
                 device=None):
        import jax
        import jax.numpy as jnp
        from .histogram import _pack_codes

        tables = build_ingest_tables(mappers, num_cols)
        self.tables = tables
        self.n_rows = int(n_rows)
        self.out_dtype = np.dtype(out_dtype)
        self.code_mode = code_mode
        put = (lambda a: jax.device_put(a, device)) if device is not None \
            else jnp.asarray
        nan_bin = put(tables.nan_bin)
        has_cat = tables.has_categorical
        if has_cat:
            is_cat = put(tables.is_cat)
            cat_vals = put(tables.cat_vals)
            cat_bins = put(tables.cat_bins)
            cat_last = put(tables.cat_last)
            cat_hi = put(tables.cat_hi)
        jnp_dtype = self.out_dtype
        n_valid = jnp.int32(self.n_rows)

        th_rows = put(np.ascontiguousarray(tables.thresholds.T))  # [T, C]

        def count_below(sv):
            # the compare-sum of the module docstring, one threshold row a
            # step: the count of thresholds strictly below the value —
            # exactly searchsorted(side="left") over the floored-f32
            # thresholds
            def step(k, pos):
                row = jax.lax.dynamic_index_in_dim(th_rows, k, 0,
                                                   keepdims=True)
                return pos + (row < sv).astype(jnp.int32)
            return jax.lax.fori_loop(
                0, th_rows.shape[0], step, jnp.zeros(sv.shape, jnp.int32))

        def _bin(chunk, offset):
            # chunk [R, C] f32, offset i32 = global row of chunk[0]
            nanm = jnp.isnan(chunk)
            sv = jnp.where(nanm, jnp.float32(0.0), chunk)
            bins = count_below(sv)
            bins = jnp.where(nanm & (nan_bin[None, :] >= 0),
                             nan_bin[None, :], bins)
            if has_cat:
                vi = jnp.where(nanm, jnp.float32(-1.0),
                               jnp.clip(chunk, jnp.float32(-1.0),
                                        cat_hi[None, :]))
                vii = vi.astype(jnp.int32)       # trunc toward zero, like np
                match = vii[:, :, None] == cat_vals[None, :, :]
                cb = jnp.sum(jnp.where(match, cat_bins[None, :, :] + 1, 0),
                             axis=2) - 1          # -1 == unseen
                cb = jnp.where((cb < 0) | (vii < 0), cat_last[None, :], cb)
                bins = jnp.where(is_cat[None, :], cb, bins)
            rows = offset + jnp.arange(chunk.shape[0], dtype=jnp.int32)
            bins = jnp.where((rows < n_valid)[:, None], bins, 0)
            codes = bins.astype(jnp_dtype)
            if code_mode is not None:
                codes = _pack_codes(codes, code_mode)
            return codes

        self._fn = jax.jit(_bin)

    def bin_chunk(self, chunk, offset: int):
        """Codes (or packed bytes) for one device-resident raw chunk."""
        return self._fn(chunk, np.int32(offset))

    @property
    def compiles(self) -> Optional[int]:
        try:
            return int(self._fn._cache_size())
        except Exception:
            return None


# ------------------------------------------------------------ chunk feeder

class ChunkFeeder:
    """Double-buffered H2D feed of raw row chunks — the ingest twin of
    ``ops/stream.ShardPrefetcher`` (same stall accounting, same honesty
    knob). ``prefetch(j)`` is called right after the driver dispatches
    chunk ``i``'s bin+pack, so chunk ``j``'s copy rides under it; a
    ``get`` that finds nothing pending transfers synchronously inside a
    counted, timed stall (``ingest.stalls`` / ``ingest.stall_seconds``).
    ``LGBM_TPU_INGEST_NO_PREFETCH=1`` turns every transfer into a measured
    stall (bench.py --ingest's no-overlap arm). Chunks select the used
    feature columns, cast to f32 (exact under the losslessness gate), and
    zero-fill the tail — the kernel's row mask makes tail content
    irrelevant, zeros keep the bytes deterministic."""

    def __init__(self, raw: np.ndarray, real_indices: np.ndarray, *,
                 chunk_rows: int, n_chunks: int, num_cols: int,
                 device=None, prefetch_enabled: Optional[bool] = None,
                 depth: int = 1):
        self.raw = raw
        self.real_indices = np.asarray(real_indices, np.int64)
        # every column used, in order (no trivial feature dropped): a chunk
        # is then a row slice, cast as it is copied, and not a fancy-indexed
        # gather of every column (0.17 s a 4,096 x 2,000 float64 chunk)
        self._all_columns = bool(
            raw.ndim == 2 and len(self.real_indices) == raw.shape[1]
            and np.array_equal(self.real_indices,
                               np.arange(raw.shape[1])))
        self.chunk_rows = int(chunk_rows)
        self.n_chunks = int(n_chunks)
        self.num_cols = int(num_cols)
        self.device = device
        if prefetch_enabled is None:
            prefetch_enabled = os.environ.get(
                "LGBM_TPU_INGEST_NO_PREFETCH", "") not in ("1", "true")
        self.prefetch_enabled = prefetch_enabled and depth > 0
        self.depth = max(1, int(depth))
        self._pending: Dict[int, object] = {}
        self.stalls = 0
        self.hits = 0
        self.stall_seconds = 0.0
        self.bytes_h2d = 0

    def _obs(self):
        from .. import observability as obs
        return obs

    def _host_chunk(self, i: int) -> np.ndarray:
        R, C = self.chunk_rows, self.num_cols
        a = i * R
        b = min(a + R, self.raw.shape[0])
        block = np.zeros((R, C), np.float32)
        if b > a:
            sel = self.raw[a:b]
            if not self._all_columns:
                sel = sel[:, self.real_indices]
            block[: b - a, : sel.shape[1]] = sel
        return block

    def _put(self, i: int):
        import jax
        block = self._host_chunk(i)
        self.bytes_h2d += block.nbytes
        self._obs().inc("ingest.bytes_h2d", block.nbytes)
        if self.device is not None:
            return jax.device_put(block, self.device)
        return jax.device_put(block)

    def prefetch(self, j: int) -> None:
        """Issue chunk ``j``'s H2D copy if not already pending; at most
        ``depth`` transfers stay in flight (depth 1 == double buffering —
        deeper queues pin host+device memory without hiding more
        latency)."""
        if not self.prefetch_enabled or not (0 <= j < self.n_chunks):
            return
        if j not in self._pending:
            if len(self._pending) >= self.depth + 1:   # defensive bound
                self._pending.clear()
            self._pending[j] = self._put(j)

    def get(self, i: int):
        """Device buffer of chunk ``i`` — prefetched if the overlap
        worked, a counted timed stall if not."""
        obs = self._obs()
        arr = self._pending.pop(i, None)
        if arr is not None:
            self.hits += 1
            obs.inc("ingest.prefetch_hits")
            return arr
        self.stalls += 1
        obs.inc("ingest.stalls")
        t0 = obs.clock()
        with obs.span("ingest_stall", chunk=i):
            arr = self._put(i)
            arr.block_until_ready()
        dt = obs.clock() - t0
        self.stall_seconds += dt
        obs.get_registry().histogram("ingest.stall_seconds").observe(dt)
        return arr

    def report(self) -> Dict:
        return {"n_chunks": self.n_chunks, "chunk_rows": self.chunk_rows,
                "stalls": self.stalls, "prefetch_hits": self.hits,
                "stall_seconds": round(self.stall_seconds, 6),
                "bytes_h2d": self.bytes_h2d,
                "prefetch_enabled": self.prefetch_enabled}


# ----------------------------------------------------------------- driver

def resolve_chunk_rows(requested: int, n_rows_padded: int,
                       num_cols: int) -> int:
    """Chunk row count: the config value, or auto-sized so one raw f32
    chunk stays near a fixed byte budget. Chunk size never changes the
    produced codes — only compile shape and overlap granularity."""
    if requested > 0:
        R = int(requested)
    else:
        R = _CHUNK_BUDGET_BYTES // max(1, 4 * num_cols)
        R = max(_CHUNK_MIN, min(_CHUNK_MAX, (R // 256) * 256))
    return max(1, min(R, max(n_rows_padded, 1)))


def device_ingest(raw: np.ndarray, mappers: Sequence[BinMapper],
                  real_indices: np.ndarray, *, n_rows: int,
                  n_rows_padded: int, num_cols: int, out_dtype,
                  chunk_rows: int = 0, device=None,
                  prefetch_depth: int = 1,
                  code_mode: Optional[str] = None,
                  ingestor: Optional[DeviceIngestor] = None):
    """Bin + pack ``raw`` on device into the residency layout.

    Returns ``(codes, report)`` where ``codes`` is the
    ``[n_rows_padded, num_cols]`` device array (or the packed byte layout
    under ``code_mode``) bit-identical to host binning + ``np.pad`` +
    ``device_put``, and ``report`` carries the throughput/overlap numbers
    (``bench.py --ingest``, ``--smoke``'s ingest leg). The caller owns the
    mesh layout: boosting/gbdt.py calls this once per device with that
    device's row block and assembles the sharded array from the pieces."""
    import jax.numpy as jnp
    from .. import observability as obs

    R = resolve_chunk_rows(chunk_rows, n_rows_padded, num_cols)
    n_chunks = max(1, -(-n_rows_padded // R))
    # a caller-supplied (already-warm) ingestor lets bench.py --ingest time
    # a steady pass without re-paying the jit compile
    ing = ingestor if ingestor is not None else DeviceIngestor(
        mappers, num_cols=num_cols, n_rows=n_rows,
        out_dtype=out_dtype, code_mode=code_mode, device=device)
    feeder = ChunkFeeder(raw, real_indices, chunk_rows=R, n_chunks=n_chunks,
                         num_cols=num_cols, device=device,
                         depth=prefetch_depth)
    t0 = obs.clock()
    with obs.setup_span("ingest", rows=int(n_rows), chunks=int(n_chunks)):
        feeder.prefetch(0)
        outs = []
        for i in range(n_chunks):
            chunk = feeder.get(i)
            if i == 0:
                # the first call traces and compiles the bin kernel (or
                # loads it from the cache) before it enqueues
                with obs.setup_span("ingest.compile"):
                    out = ing.bin_chunk(chunk, 0)
            else:
                out = ing.bin_chunk(chunk, i * R)
            for j in range(i + 1, min(i + 1 + feeder.depth, n_chunks)):
                feeder.prefetch(j)       # copy rides under chunk i's compute
            outs.append(out)
        codes = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
        if codes.shape[0] != n_rows_padded:
            codes = codes[:n_rows_padded]
        codes.block_until_ready()
    seconds = obs.clock() - t0
    obs.inc("ingest.rows", int(n_rows))
    obs.inc("ingest.chunks", int(n_chunks))
    rep = feeder.report()
    rep.update({
        "rows": int(n_rows), "rows_padded": int(n_rows_padded),
        "num_cols": int(num_cols), "seconds": round(seconds, 6),
        "rows_per_s": (float(n_rows) / seconds) if seconds > 0 else None,
        "stall_fraction": (rep["stall_seconds"] / seconds)
        if seconds > 0 else 0.0,
        "compiles": ing.compiles,
    })
    Log.debug("device ingest: %d rows in %d x %d-row chunks (%.3fs, "
              "%d stalls, %.1f MB H2D)", n_rows, n_chunks, R, seconds,
              rep["stalls"], rep["bytes_h2d"] / (1 << 20))
    return codes, rep


def merge_ingest_reports(reports: Sequence[Dict]) -> Dict:
    """One report for a (row-sharded) ingest: per-device ``device_ingest``
    reports run back to back, so counts and seconds add; ``compiles`` is
    the worst single ingestor (each device owns one executable that all of
    its chunks must share)."""
    out = dict(reports[0])
    for key in ("n_chunks", "stalls", "prefetch_hits", "bytes_h2d", "rows",
                "rows_padded"):
        out[key] = sum(r[key] for r in reports)
    seconds = sum(r["seconds"] for r in reports)
    stall = sum(r["stall_seconds"] for r in reports)
    compiles = [r["compiles"] for r in reports]
    out.update({
        "devices": len(reports),
        # one device after another (boosting/gbdt._ingest_device): their
        # seconds add up to the wall clock's
        "device_seconds": [r["seconds"] for r in reports],
        "in_turn": True,
        "seconds": round(seconds, 6),
        "stall_seconds": round(stall, 6),
        "rows_per_s": (out["rows"] / seconds) if seconds > 0 else None,
        "stall_fraction": (stall / seconds) if seconds > 0 else 0.0,
        "compiles": None if None in compiles else max(compiles),
    })
    return out
