"""Out-of-core shard transport: host-resident packed bin codes, streamed
H2D through a double-buffered prefetcher (``tpu_residency=stream``).

The design point comes straight from the out-of-core GBDT literature:
"Out-of-Core GPU Gradient Boosting" (arXiv 2005.09148) shows a chunked
host-resident pipeline loses only a few percent when transfers overlap
compute, and "XGBoost: Scalable GPU Accelerated Learning" (arXiv
1806.11248) pins what to stream — keep gradients/partition state
device-resident and move ONLY the compressed bin codes. Three pieces:

- :func:`pack_codes_host` — numpy twin of ``ops/histogram._pack_codes``
  (u8 | u16 | u4 | u6 byte layouts), so shards transfer at 0.5-2 bytes per
  code and ``unpack_codes`` on device restores the exact integer codes
  (parity pinned in tests/test_stream.py).
- :class:`HostShardStore` — the padded code matrix cut into fixed-size row
  shards. Under row-sharded strategies (tree_learner=data|voting) each
  shard interleaves the per-DEVICE blocks of the resident layout, so
  ``device_put`` with the booster's row sharding hands device d exactly
  the rows it would hold resident — the per-device histogram fold order
  (and therefore the trained model) is bit-identical to device residency.
- :class:`ShardPrefetcher` — double-buffered ``jax.device_put``: the
  driver (grower.StreamedGrower) calls ``prefetch(i+1)`` right after
  dispatching shard i's compute, so the H2D copy of the next shard rides
  under the current shard's histogram matmul. ``get(i)`` that finds no
  prefetched buffer is a *stall* — counted (``stream.stalls``) and timed
  (``stream.stall_seconds``) so the overlap is measured, not assumed
  (``bench.py --stream`` reports the stall fraction). Buffers are NEVER
  donated to jitted fns (the same buffer is handed out again next wave),
  which is what makes the ping-pong donation-safe.

Integrity: each packed shard carries a CRC32 taken at pack time, re-checked
before EVERY transfer (``tpu_stream_verify``, on by default). A mismatch
raises the typed :class:`ShardCorruptionError` instead of folding
bit-rotted codes into histograms; the chaos harness (robustness/chaos.py
``corrupt_host_shard``) flips shard bytes in flight to exercise exactly
this path. The check is NOT free: zlib.crc32 runs ~1 GB/s on one host
core — the same order as the copy it precedes — and it is synchronous in
the training thread, so at host-RAM-scale stores it is a measurable tax
(``bench.py --stream`` prices it on the real shape); set
``tpu_stream_verify=false`` to trade detection for that throughput.

This module and ``dataset.py`` are the only sanctioned homes of
``jax.device_put`` reachable from wave/scan bodies — tpu-lint R009
enforces that the prefetcher stays the single choke point for mid-loop
host->device traffic.
"""
from __future__ import annotations

import zlib
from typing import Callable, Dict, List, Optional

import numpy as np

from ..utils.log import Log


class ShardCorruptionError(RuntimeError):
    """A host-resident code shard failed its CRC32 integrity check at
    transfer time: the bytes about to be fed to the histogram fold are not
    the bytes that were packed (host memory corruption, a stray writer).
    Training must stop — a silently corrupted shard poisons every later
    tree. The store is rebuilt from the dataset at construction, so a
    restart (the crash supervisor relaunches with ``resume_from=auto``)
    self-heals; the CLI exits with status 144 on this error."""

def pack_codes_host(X: np.ndarray, code_mode: str) -> np.ndarray:
    """[N, F] uint8/uint16 bin codes -> [N, code_bytes_total(F, mode)] u8.

    Byte-for-byte identical to the device-side ``_pack_codes``
    (ops/histogram.py) so ``unpack_codes`` inverts it exactly; numpy so the
    host shard store never touches a device. Little-endian u16, low-nibble-
    first u4, and the 4-codes-in-3-bytes u6 layout all match."""
    X = np.ascontiguousarray(X)
    N, F = X.shape
    if code_mode == "u8":
        return X.astype(np.uint8, copy=False)
    if code_mode == "u16":
        return X.astype("<u2", copy=False).view(np.uint8).reshape(N, 2 * F)
    x = X.astype(np.uint8, copy=False)
    if code_mode == "u4":
        if F % 2:
            x = np.pad(x, ((0, 0), (0, 1)))
        return (x[:, 0::2] | (x[:, 1::2] << 4)).astype(np.uint8)
    assert code_mode == "u6", code_mode
    if F % 4:
        x = np.pad(x, ((0, 0), (0, 4 - F % 4)))
    q = x.reshape(N, -1, 4).astype(np.uint8)
    c0, c1, c2, c3 = q[..., 0], q[..., 1], q[..., 2], q[..., 3]
    b0 = c0 | (c1 << 6)
    b1 = (c1 >> 2) | (c2 << 4)
    b2 = (c2 >> 4) | (c3 << 2)
    return np.stack([b0, b1, b2], axis=-1).reshape(N, -1).astype(np.uint8)


# ------------------------------------------------------------ shard geometry

def resolve_shard_rows(per_device_rows: int, chunk_rows: int,
                       requested_rows: int = 0) -> int:
    """Per-device rows of one shard: a multiple of ``chunk_rows`` that
    DIVIDES ``per_device_rows`` exactly.

    Divisibility is a correctness constraint, not a convenience: the
    padded row count (and with it every chunk boundary and the bagging
    RNG's draw shapes) must be IDENTICAL to device residency, or streamed
    training would not be bit-identical. ``requested_rows`` (config
    ``tpu_stream_shard_rows``, interpreted per device) rounds to the
    NEAREST achievable divisor (ties break toward finer shards — more
    prefetch slack, smaller buffers); 0 auto-sizes toward ~8 shards.
    Since shard size never changes the math, a checkpoint resumes under
    ANY shard size (docs/Fault-Tolerance.md)."""
    assert per_device_rows % chunk_rows == 0, (per_device_rows, chunk_rows)
    m = per_device_rows // chunk_rows          # total chunks per device
    if requested_rows <= 0:
        want = m / 8.0                         # ~8 shards by default
    else:
        want = min(float(m), requested_rows / chunk_rows)
    # divisor of m NEAREST to want (not largest-below: a prime-ish m
    # would otherwise degenerate to m single-chunk shards)
    best = 1
    for c in range(1, int(m ** 0.5) + 1):
        if m % c == 0:
            for d in (c, m // c):
                if (abs(d - want), d) < (abs(best - want), best):
                    best = d
    return best * chunk_rows


class HostShardStore:
    """The padded, packed code matrix as fixed-size host row shards.

    ``X`` is the RAW [N, F] host code matrix; padding (rows to
    ``n_rows_padded``, columns to ``num_cols`` — exactly what device
    residency would ``np.pad`` before ``device_put``) is applied
    per-block at pack time, so the store never materializes a full padded
    copy: at >HBM dataset scale (the whole point of streaming) the host
    working set is the packed shards (0.5-2 B/code) plus ONE transient
    unpacked block. ``local_shard_rows`` is the PER-DEVICE rows of one
    shard; a shard's global row count is ``local_shard_rows *
    n_devices``. Under ``n_devices > 1`` shard i interleaves each
    device's i-th sub-block so the booster's row sharding places device
    d's resident rows back on device d (see module doc).
    """

    def __init__(self, X: np.ndarray, *, n_rows_padded: int, num_cols: int,
                 local_shard_rows: int, n_devices: int, code_mode: str):
        n_real, f_real = X.shape
        assert n_rows_padded >= n_real and num_cols >= f_real
        assert n_rows_padded % n_devices == 0
        per_dev = n_rows_padded // n_devices
        assert per_dev % local_shard_rows == 0, (per_dev, local_shard_rows)
        self.n_rows_padded = n_rows_padded
        self.num_cols = num_cols
        self.n_devices = n_devices
        self.local_shard_rows = local_shard_rows
        self.n_shards = per_dev // local_shard_rows
        self.code_mode = code_mode
        self.dtype = X.dtype
        R = local_shard_rows

        # ONE reused [shard_rows, num_cols] staging buffer: each shard's
        # device sub-blocks are strided writes into it (no per-block zeros
        # allocation, no per-shard concatenate — the transient unpacked
        # working set is exactly one shard). Padding rows/cols are the
        # zeros device residency pads with; the buffer only needs
        # re-zeroing when padding exists at all (otherwise every element
        # is overwritten).
        needs_zero = n_rows_padded > n_real or num_cols > f_real
        block = np.zeros((R * n_devices, num_cols), X.dtype)
        shards: List[np.ndarray] = []
        for i in range(self.n_shards):
            if needs_zero and i:
                block[:] = 0
            for d in range(n_devices):
                a = d * per_dev + i * R
                if a < n_real:
                    rows = X[a:min(a + R, n_real)]
                    block[d * R: d * R + rows.shape[0], :f_real] = rows
            packed = pack_codes_host(block, code_mode)
            if packed is block or packed.base is not None:
                # u8/u16 packing returns the input (or a bitcast view of
                # it) — materialize a copy or the next shard's strided
                # writes would clobber this one
                packed = packed.copy()
            shards.append(np.ascontiguousarray(packed))
        self.shards = shards
        self.shard_bytes = int(shards[0].nbytes) if shards else 0
        # per-shard content checksum, taken at pack time: the prefetcher
        # re-hashes each shard before every H2D transfer, so a bit flipped
        # in host RAM between packing and streaming is DETECTED (typed
        # ShardCorruptionError) instead of silently folded into histograms
        self.checksums: List[int] = [self._crc(s) for s in shards]

    @staticmethod
    def _crc(shard: np.ndarray) -> int:
        return zlib.crc32(shard) & 0xFFFFFFFF

    def verify_shard(self, i: int) -> bool:
        """Recompute shard ``i``'s CRC32 and compare with the pack-time
        value. Costs ~shard_bytes / 1 GB/s of synchronous host CPU — see
        the module docstring for the honest per-iteration price."""
        return self._crc(self.shards[i]) == self.checksums[i]

    @property
    def total_bytes(self) -> int:
        return self.shard_bytes * self.n_shards

    def describe(self) -> Dict:
        return {"n_shards": self.n_shards,
                "shard_rows": self.local_shard_rows * self.n_devices,
                "shard_bytes": self.shard_bytes,
                "code_mode": self.code_mode,
                "total_bytes": self.total_bytes}


class ShardPrefetcher:
    """Double-buffered H2D feed over a :class:`HostShardStore`.

    ``put_fn(np_shard) -> jax.Array`` is supplied by the booster and
    applies its row sharding (``jax.device_put`` with the mesh
    NamedSharding) — this class never decides placement. At most two shard
    buffers are live: the one compute is consuming and the one in flight.

    Access pattern contract: shards are read cyclically 0..n-1 (one cycle
    per wave, plus the trailing route pass). ``get(i)`` returns shard i's
    device buffer, preferring the prefetched one; ``prefetch(j)`` issues
    shard ``j % n_shards``'s transfer and is a no-op when it is already
    pending. A ``get`` that finds nothing pending is a STALL: the transfer
    runs synchronously in the caller's critical path, counted and timed
    into the registry (``stream.stalls`` / ``stream.stall_seconds``
    histogram) under a ``prefetch_stall`` span. ``stream.bytes_h2d``
    counts every transferred byte either way.

    ``LGBM_TPU_STREAM_NO_PREFETCH=1`` turns ``prefetch`` into a no-op —
    every shard transfer becomes a measured stall. That is the honesty
    knob behind ``bench.py --stream``'s overlap-vs-no-overlap comparison
    and the forced-stall tests.
    """

    def __init__(self, store: HostShardStore, put_fn: Callable,
                 prefetch_enabled: Optional[bool] = None,
                 verify: bool = True):
        import os
        self.store = store
        self.put_fn = put_fn
        if prefetch_enabled is None:
            prefetch_enabled = os.environ.get(
                "LGBM_TPU_STREAM_NO_PREFETCH", "") not in ("1", "true")
        self.prefetch_enabled = prefetch_enabled
        self.verify_enabled = verify
        self._pending: Dict[int, object] = {}
        self.stalls = 0
        self.hits = 0
        self.stall_seconds = 0.0
        self.bytes_h2d = 0

    def _registry(self):
        from .. import observability as obs
        return obs

    def _put(self, i: int):
        if self.verify_enabled and not self.store.verify_shard(i):
            obs = self._registry()
            obs.inc("fault.shard_corrupt")
            obs.event("shard_corrupt", shard=i)
            raise ShardCorruptionError(
                f"host shard {i} failed its CRC32 integrity check "
                f"(expected {self.store.checksums[i]:#010x}) — the packed "
                f"codes changed in host memory since construction; "
                f"restart the run (resume_from=auto rebuilds the shard "
                f"store from the dataset; tpu_stream_verify=false disables "
                f"this check)")
        self.bytes_h2d += self.store.shard_bytes
        self._registry().inc("stream.bytes_h2d", self.store.shard_bytes)
        return self.put_fn(self.store.shards[i])

    def prefetch(self, j: int) -> None:
        """Issue shard ``j % n_shards``'s H2D copy if not already pending.
        Called right AFTER the driver dispatches compute on the current
        shard, so the copy overlaps it; at most one transfer is kept in
        flight (double buffering — buffer 3 would just pin host+device
        memory without hiding any more latency)."""
        if not self.prefetch_enabled or not self.store.n_shards:
            return
        j = j % self.store.n_shards
        if j not in self._pending:
            if len(self._pending) >= 2:      # defensive: contract is <= 1
                self._pending.clear()
            self._pending[j] = self._put(j)

    def get(self, i: int):
        """Device buffer of shard ``i`` — prefetched if the overlap worked,
        synchronously transferred (a counted, timed stall) if not."""
        obs = self._registry()
        arr = self._pending.pop(i, None)
        if arr is not None:
            self.hits += 1
            obs.inc("stream.prefetch_hits")
            return arr
        self.stalls += 1
        obs.inc("stream.stalls")
        t0 = obs.clock()
        with obs.span("prefetch_stall", shard=i):
            arr = self._put(i)
            # block on THIS transfer only (compute stays queued): the wait
            # is the measurable cost the double buffer exists to hide
            # (jax's own helper: an injected put_fn may hand back host arrays)
            import jax
            jax.block_until_ready(arr)
        dt = obs.clock() - t0
        self.stall_seconds += dt
        obs.get_registry().histogram("stream.stall_seconds").observe(dt)
        return arr

    def report(self) -> Dict:
        return {"n_shards": self.store.n_shards,
                "shard_bytes": self.store.shard_bytes,
                "stalls": self.stalls, "prefetch_hits": self.hits,
                "stall_seconds": round(self.stall_seconds, 6),
                "bytes_h2d": self.bytes_h2d,
                "prefetch_enabled": self.prefetch_enabled,
                "verify_enabled": self.verify_enabled}
