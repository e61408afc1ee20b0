"""Gradient/hessian histogram construction as MXU one-hot matmuls.

The TPU replacement for the reference's histogram kernels:
- CPU scatter-add: Bin::ConstructHistogram (src/io/dense_bin.hpp:66-130)
- OpenCL local-memory atomics (src/treelearner/ocl/histogram256.cl:95-125)

TPUs have no fast scatter (an earlier on-chip session measured it ~400x
slower than the matmul formulation), so the histogram is computed as a
chunked one-hot matmul:

    hist[f, b, s*ch+j] = sum_r (X[r,f] == b) * rhs[r, s*ch+j]

where `rhs` carries per-leaf-slot weight columns: rows whose leaf is assigned
slot `s` contribute their (gradient, hessian, count) channels to that slot's
columns, everyone else contributes zero. One pass over the data therefore
builds histograms for up to S leaves at once — the TPU analog of the
reference's "histogram for the smaller leaf, sibling by subtraction" pipeline
(src/treelearner/serial_tree_learner.cpp:354-362).

Precision: the one-hot matrix is exact in bf16; gradients/hessians are split
into bf16 hi+lo pairs accumulated in f32, giving ~f32-accurate sums at full
MXU speed (the reference GPU path used plain f32 atomics and accepted small
accuracy deltas: docs/GPU-Performance.rst:131-133).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

# Weight-channel modes (the `exact` parameter throughout):
#   False — g_hi, g_lo, h_hi, h_lo, count bf16 hi/lo pairs (~f32 sums)
#   True  — g, h, count full f32 columns contracted at Precision.HIGHEST
#           (exact per-element products; tpu_hist_f64's exactness half —
#           the Kahan carry in build_histograms is the other)
NUM_CHANNELS = 5
NUM_CHANNELS_EXACT = 3


def num_channels(exact: bool) -> int:
    return NUM_CHANNELS_EXACT if exact else NUM_CHANNELS


# ---- the pass, sized by the table's width ----------------------------------
# One chunk of the pass is one matmul of a [chunk_rows, F, B] one-hot operand
# against [chunk_rows, S*ch] weight columns into the [F, B, S*ch] f32
# accumulator. The TPU fuses the operand's producer into the matmul and
# allocates none of it (temporaries 0.68 GiB at 2,000 columns whatever the
# chunk; PERF.md, PR 30), so its size is no memory bound there; the CPU
# backend allocates it whole. What the chunk decides on the chip is the
# pass's grain: the rows are padded to a whole number of chunks and a
# compacted pass cannot be shorter than one, so a 32,768-row chunk of a
# 400,000-row table pads 6.5% and makes every late wave cost a thirteenth
# of the table. The rule holds chunk_rows x F x B x channel bytes to
# _ONEHOT_BYTES_A_CHUNK: 32,768 rows up to 256 columns at 256 bins in bf16
# (every table measured before PR 30), fewer rows a chunk beyond.
_ONEHOT_BYTES_A_CHUNK = 4 << 30


def hist_pass_shape(rows: int, features: int, bins_padded: int,
                    channel_bytes: int, max_chunk: int) -> Tuple[int, str]:
    """(rows a chunk, the rule that chose them) of the one-hot matmul pass
    over ``rows`` rows a device: a static function of the shapes.
    ``max_chunk`` (``tpu_hist_chunk``) is an upper bound on the chunk."""
    chunk = min(int(max_chunk), -(-int(rows) // 256) * 256)
    by_width = max(256, _ONEHOT_BYTES_A_CHUNK
                   // (int(features) * int(bins_padded) * int(channel_bytes))
                   // 256 * 256)
    if by_width >= chunk:
        return chunk, "max_chunk"
    return by_width, "width"


# ---- stream or compact: which arm builds a wave's histograms ---------------
# A wave with n of a device's N rows pending can STREAM the pass over all N
# rows, or COMPACT it: sort the rows by pending slot once (N keys), then run
# ceil(n / chunk) chunks whose rows come by a row gather from the packed
# array. The compacted arm is the cheaper one when
#     n x (matmul + gather) + N x sort  <  N x (matmul + stream_fixed)
# with every term in ns a row as read ON THE v5e (PERF.md, PR 31: whole
# trees at four shapes with the threshold swept, each arm's pass alone at
# sixteen widths, one gather alone at thirty-five row widths):
#   matmul        _MATMUL_NS_A_CELL x F x B, the chunk matmul of either arm
#                 with bf16 hi/lo weights (1.36 ps a cell in the trees of
#                 400,000 x 2,000, 1.40 in passes alone from 6 to 512
#                 columns), times _MATMUL_SCALE_EXACT with f32 columns at
#                 Precision.HIGHEST
#   stream_fixed  what a streamed row pays whatever the width: its slot
#                 lookup, slices and weight channels
#   gather        one packed row, BY ITS BYTES and not smoothly: rows of 28
#                 to 59 bytes cost three times what rows of 60 to 128 do
#   sort          the one-word sort and the per-slot counts; (slot, row)
#                 pairs beyond 2^24 rows a device or 127 slots
# So the threshold follows the width (a wasted streamed row is dearer the
# wider the table) and the packed row. Every wave but the root histograms
# smaller children only, under half of the rows: a value above 0.5 means
# "stream a full root, compact the rest".
_MATMUL_NS_A_CELL = 1.38e-3
_MATMUL_SCALE_EXACT = 5.1
_STREAM_FIXED_NS = 1.8
# (row bytes up to, ns a gathered row): one gather alone at each width, times
# the 0.9 that whole trees read of it at 20 and at 38 bytes
_GATHER_NS_BY_ROW_BYTES = (
    (8, 4.0), (16, 5.0), (24, 9.0), (32, 24.4), (40, 31.0), (48, 36.0),
    (59, 38.3), (128, 11.5), (256, 13.8))
_GATHER_NS_A_BYTE_BEYOND = 0.0138              # 38 ns at 2,010 bytes
_SORT_NS_A_ROW = {True: 1.0, False: 3.1}       # one word | (slot, row) pairs
_MIN_COMPACT_FRAC = 1.0 / 64
# the Pallas / mixed kernels size their skip-grid buffers for N/4 rows
PALLAS_COMPACT_FRAC_CAP = 0.25


def sort_is_one_word(rows: int, num_slots: int) -> bool:
    """Slot and row number fit one int32 key (``slot << 24 | row``)."""
    return rows <= 1 << 24 and num_slots < 1 << 7


def _gather_ns(row_bytes: int) -> float:
    for upto, ns in _GATHER_NS_BY_ROW_BYTES:
        if row_bytes <= upto:
            return ns
    return ns + _GATHER_NS_A_BYTE_BEYOND * (row_bytes - upto)


def compact_break_even(rows: int, features: int, bins_padded: int,
                       row_bytes: int, num_slots: int,
                       exact: bool = False) -> float:
    """The share of a device's rows below which a wave's compacted pass is
    cheaper than a streamed one: a static function of the shapes, in
    (0, 1]. ``features`` x ``bins_padded`` is the histogram BUILD's width
    (bundle space under EFB), ``row_bytes`` the packed row the gather
    fetches, ``rows`` the rows a device, ``exact`` the weight mode."""
    matmul = (_MATMUL_NS_A_CELL * int(features) * int(bins_padded)
              * (_MATMUL_SCALE_EXACT if exact else 1.0))
    sort = _SORT_NS_A_ROW[sort_is_one_word(int(rows), int(num_slots))]
    frac = ((matmul + _STREAM_FIXED_NS - sort)
            / (matmul + _gather_ns(int(row_bytes))))
    return float(min(1.0, max(_MIN_COMPACT_FRAC, frac)))


def resolve_compact_frac(requested: float, hist_kernel: str, **shape) -> float:
    """``tpu_compact_frac`` as the grower takes it: an explicit value as it
    is, 0 (auto) as ``compact_break_even`` of the shapes; either way under
    the Pallas kernels' cap where they build the compacted passes."""
    frac = float(requested) or compact_break_even(**shape)
    if hist_kernel in ("pallas", "mixed"):
        frac = min(frac, PALLAS_COMPACT_FRAC_CAP)
    return frac


# ---- the one-leaf form of the chunk matmul ---------------------------------
# A wave whose pending leaves number ONE (the root's pass, its smaller
# child's) pays the 125 columns of the general form for 5 live ones: rhs is
# [R, S*ch] and every row of the wave is in slot 0. The one-leaf form splits
# the bin code instead, code = hi << 3 | lo, and contracts two NARROW
# one-hots over the rows, G features side by side:
#     hist[f, hi, lo, c] = sum_r [hi_rf == hi] * ([lo_rf == lo] * w[r, c])
# lhs G x bins_hi wide (96 at 256 bins), rhs G x 5 x 8 = 120 columns: ONE
# 128 x 128 MXU tile pass per G = 3 features and 128 rows where the general
# form streams 256 one-hot columns a feature; the off-diagonal feature blocks
# of the [G*bins_hi, 128] product are discarded. Same five bf16 hi/lo
# channels, 0/1 one-hots, f32 accumulation: every product exact, only the
# order of the f32 additions inside a chunk differs. Both one-hots are built
# in VMEM by a Pallas kernel (ops/pallas_histogram.hist_one_leaf_chunk):
# every XLA form of the split materialises the weight operand or lowers the
# batched dot to a dilated convolution, and ran 1.6 to 5 times SLOWER than
# the general form (PERF.md, PR 37). Read on the v5e, one pass alone, ns a
# row streamed / compacted (gather included), slices, weight rows and the
# per-chunk transposes included:
#     14,680,064 x 67:   4.00 / 14.17   (the general form 25.42 / 34.94)
#     401,408 x 2,000:   113.7 / 166.1  (707.5 / 764.2)
# = 2.2e-4 ns a PADDED cell (72 x 256, 2,016 x 256). (With its loop over
# the feature groups unrolled whole the kernel read 3.70 / 13.86 and 103.0
# / 155.1, and cost every process 16 s of tracing: pallas_histogram.py.)
_ONE_LEAF_LO_BITS = 3
_ONE_LEAF_NS_A_CELL = 2.2e-4
_ONE_LEAF_ROW_TILES = (2048, 1024, 512, 256, 128)
ONE_LEAF_TRIP_GROUPS = 4            # feature groups a trip of the kernel's
                                    # loop over a grid step's block of groups
_ONE_LEAF_MAX_BLOCK_GROUPS = 32     # feature groups a grid step


class OneLeafForm(NamedTuple):
    """The one-leaf form's static shapes for one table."""
    bins_lo: int          # 1 << _ONE_LEAF_LO_BITS
    bins_hi: int          # ceil(bins / bins_lo), padded to 16 sublanes
    group: int            # G: features side by side in one matmul
    groups: int           # feature groups, padded to whole blocks
    block_groups: int     # groups a grid step of the kernel covers
    row_tile: int         # rows a grid step

    @property
    def features_padded(self) -> int:
        return self.groups * self.group

    @property
    def acc_shape(self) -> Tuple[int, int, int]:
        return (self.groups, self.group * self.bins_hi, 128)

    @property
    def acc_bytes(self) -> int:
        g, m, n = self.acc_shape
        return g * m * n * 4


def one_leaf_form(features: int, bins_padded: int, chunk_rows: int
                  ) -> Optional[OneLeafForm]:
    """The one-leaf form for a histogram build of ``features`` x
    ``bins_padded`` in chunks of ``chunk_rows``, or None where it has none:
    a static function of the shapes. A bin count the split does not divide
    and a feature count G does not divide are padded (the padding's cells
    are computed and dropped); a chunk no row tile divides and more than
    1,024 bins (a hi one-hot wider than one MXU tile) keep the general
    form."""
    bins_lo = 1 << _ONE_LEAF_LO_BITS
    bins_hi = -(-(-(-int(bins_padded) // bins_lo)) // 16) * 16
    row_tile = next((t for t in _ONE_LEAF_ROW_TILES
                     if int(chunk_rows) % t == 0), None)
    if row_tile is None or bins_hi > 128 or features < 1:
        return None
    group = max(1, min(128 // (bins_lo * NUM_CHANNELS), 128 // bins_hi))
    # a block is whole trips of the kernel's loop over its groups
    n_groups = -(-int(features) // group)
    block = min(-(-n_groups // ONE_LEAF_TRIP_GROUPS) * ONE_LEAF_TRIP_GROUPS,
                _ONE_LEAF_MAX_BLOCK_GROUPS)
    return OneLeafForm(bins_lo=bins_lo, bins_hi=bins_hi, group=group,
                       groups=-(-n_groups // block) * block,
                       block_groups=block, row_tile=row_tile)


def one_leaf_break_even(rows: int, form: OneLeafForm, row_bytes: int,
                        num_slots: int) -> float:
    """``compact_break_even`` for a ONE-LEAF wave: the share of a device's
    rows below which its compacted pass is cheaper than its streamed one,
    with the form's own cost a row in place of the general matmul's. The
    streamed form pays nothing beside the matmul (no slot lookup), so a
    cheap matmul leaves the gather and the sort to decide: 0.20 at 67
    columns (the root's smaller child, 0.3-0.5 of the rows, STREAMS; so does
    a GOSS root at 0.30), 0.74 at 2,000."""
    matmul = (_ONE_LEAF_NS_A_CELL * form.features_padded
              * form.bins_hi * form.bins_lo)
    sort = _SORT_NS_A_ROW[sort_is_one_word(int(rows), int(num_slots))]
    frac = (matmul - sort) / (matmul + _gather_ns(int(row_bytes)))
    return float(min(1.0, max(_MIN_COMPACT_FRAC, frac)))


def weight_channels(grad, hess, included, exact: bool):
    """[N, ch] weight channels for the one-hot matmul (dtype by mode)."""
    if exact:
        return jnp.stack([grad.astype(jnp.float32),
                          hess.astype(jnp.float32),
                          included.astype(jnp.float32)], axis=-1)
    g_hi, g_lo = _split_hi_lo(grad)
    h_hi, h_lo = _split_hi_lo(hess)
    # every input cast explicitly (R003): a dtype change upstream in
    # _split_hi_lo must not silently widen the packed channel matrix
    return jnp.stack([g_hi.astype(jnp.bfloat16),
                      g_lo.astype(jnp.bfloat16),
                      h_hi.astype(jnp.bfloat16),
                      h_lo.astype(jnp.bfloat16),
                      included.astype(jnp.bfloat16)], axis=-1)


def combine_channels(acc, exact: bool):
    """[..., ch] f32 accumulated channels -> [..., 3] (sum_g, sum_h, cnt)."""
    if exact:
        return acc[..., :3]
    return jnp.stack([acc[..., 0] + acc[..., 1],
                      acc[..., 2] + acc[..., 3], acc[..., 4]], axis=-1)


def _split_hi_lo(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    # reduce_precision, NOT astype(bf16).astype(f32): inside a jitted program
    # the TPU compiler (xla_allow_excess_precision) elides the f32->bf16->f32
    # round trip, which makes hi == x and lo == 0 — the sums then carry bf16
    # precision only. reduce_precision is never elided; its result is
    # exactly bf16-representable, so the cast below is exact. The CPU
    # backend keeps the round trip, so only a chip run shows the difference
    # (chip_smoke.py's Pallas leg pins both kernels against f64 sums).
    x = x.astype(jnp.float32)
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def _weight_rows(grad, hess, included, live) -> jnp.ndarray:
    """[8, R] f32: the five weight channels of ``weight_channels`` as ROWS
    (rows of the table along the lanes, as the one-leaf kernel reads them),
    zero where ``live`` is unset, three rows of padding. Every value is
    exactly bf16-representable, so the kernel's cast to bf16 is exact."""
    w = weight_channels(grad, hess, included, exact=False)          # [R, 5]
    rows = jnp.where(live[None, :], w.astype(jnp.float32).T, 0.0)
    return jnp.pad(rows, ((0, 8 - NUM_CHANNELS), (0, 0)))


# ---- packed-row form for the compacted gather -------------------------------
# A random row access to HBM costs about the same regardless of width (an
# earlier on-chip session measured ~25-55 ns), so the compacted pass gathers
# ONE packed array
# holding everything it needs per row instead of four separate gathers of
# X/grad/hess/included. The packed dtype is uint8, NOT int32: TPU tiling
# pads the minor dimension to 128 lanes, so ANY [N, small] i32 array
# materializes at N x 512 B (5.4 GB at the 10.5M-row bench) while u8 pays
# N x 128 B. Layout per row: F code bytes (2F little-endian for uint16
# codes) then 2*ch bf16 weight bytes. Packing itself is a sequential O(N)
# write, paid once per tree (grow_tree builds it and passes packed=).

def code_bytes(dtype) -> int:
    return 1 if dtype == jnp.uint8 else 2


# Code packing modes for the per-row byte layout (the reference's analog is
# the Dense4bitsBin storage, src/io/dense_nbits_bin.hpp:37 — two codes per
# byte at <=16 bins; "u6" additionally serves the reference's own GPU bench
# config max_bin=63, docs/GPU-Performance.rst:105-125, at 3 bytes per 4
# codes):
#   "u8"  1 byte/code   (any codes < 256)
#   "u16" 2 bytes/code  (max_bin > 255)
#   "u4"  1 byte/2 codes (codes < 16)
#   "u6"  3 bytes/4 codes (codes < 64)
# Packed gathers are priced per ROW BYTE by the HBM random-access tax, so
# u4/u6 cut the compacted pass's gather traffic 2x / 1.33x.

def default_code_mode(dtype) -> str:
    """Plain byte layout for a bin-code dtype (no bit packing)."""
    return "u16" if dtype == jnp.uint16 else "u8"


def code_mode_for(max_code: int, dtype) -> str:
    if dtype == jnp.uint16 or max_code > 256:
        return "u16"
    if max_code <= 16:
        return "u4"
    if max_code <= 64:
        return "u6"
    return "u8"


def code_bytes_total(F: int, code_mode: str) -> int:
    return {"u8": F, "u16": 2 * F, "u4": (F + 1) // 2,
            "u6": ((F + 3) // 4) * 3}[code_mode]


def packed_row_bytes(F: int, code_mode: str, exact: bool) -> int:
    """Bytes of one row of ``pack_rows``: the code bytes, then the weight
    channels (three f32 in the exact mode, five bf16 otherwise)."""
    return (code_bytes_total(F, code_mode)
            + num_channels(exact) * (4 if exact else 2))


def _pack_codes(X: jnp.ndarray, code_mode: str) -> jnp.ndarray:
    """[N, F] codes -> [N, code_bytes_total(F)] u8 bytes."""
    N, F = X.shape
    if code_mode == "u8":
        return X
    if code_mode == "u16":
        x16 = X.astype(jnp.uint16)
        return jax.lax.bitcast_convert_type(x16, jnp.uint8).reshape(N, 2 * F)
    x = X.astype(jnp.uint8)
    if code_mode == "u4":
        if F % 2:
            x = jnp.pad(x, ((0, 0), (0, 1)))
        return x[:, 0::2] | (x[:, 1::2] << 4)
    # u6: 4 six-bit codes -> 3 bytes
    if F % 4:
        x = jnp.pad(x, ((0, 0), (0, 4 - F % 4)))
    q = x.reshape(N, -1, 4)
    c0, c1, c2, c3 = q[..., 0], q[..., 1], q[..., 2], q[..., 3]
    b0 = c0 | (c1 << 6)
    b1 = (c1 >> 2) | (c2 << 4)
    b2 = (c2 >> 4) | (c3 << 2)
    return jnp.stack([b0, b1, b2], axis=-1).reshape(N, -1)


def pack_rows(X, grad, hess, included, exact: bool,
              code_mode: str = None) -> Tuple[jnp.ndarray, int]:
    """Returns (packed [N, ncb + weight bytes] u8, code byte count ncb)."""
    N, F = X.shape
    if code_mode is None:
        code_mode = default_code_mode(X.dtype)
    codes = _pack_codes(X, code_mode)
    w = weight_channels(grad, hess, included, exact)    # [N, ch] bf16 or f32
    wb = jax.lax.bitcast_convert_type(w, jnp.uint8).reshape(N, -1)
    return jnp.concatenate([codes, wb], axis=1), codes.shape[1]


def unpack_codes(xb: jnp.ndarray, F: int, code_mode: str) -> jnp.ndarray:
    """[R, ncb] u8 code bytes -> [R, F] i32 bin codes (inverse of
    _pack_codes)."""
    if code_mode == "u8":
        return xb.astype(jnp.int32)
    if code_mode == "u16":
        return jax.lax.bitcast_convert_type(
            xb.reshape(xb.shape[0], F, 2), jnp.uint16).astype(jnp.int32)
    R = xb.shape[0]
    if code_mode == "u4":
        out = jnp.stack([xb & 15, xb >> 4], axis=-1).reshape(R, -1)
        return out[:, :F].astype(jnp.int32)
    assert code_mode == "u6", code_mode
    t = xb.reshape(R, -1, 3)
    b0, b1, b2 = t[..., 0], t[..., 1], t[..., 2]
    c0 = b0 & 63
    c1 = (b0 >> 6) | ((b1 & 15) << 2)
    c2 = (b1 >> 4) | ((b2 & 3) << 4)
    c3 = b2 >> 2
    out = jnp.stack([c0, c1, c2, c3], axis=-1).reshape(R, -1)
    return out[:, :F].astype(jnp.int32)


def unpack_weights(wb: jnp.ndarray, ch: int, f32: bool = False) -> jnp.ndarray:
    """[R, bytes*ch] u8 -> [R, ch] bf16 (or f32) weight channels."""
    if f32:
        return jax.lax.bitcast_convert_type(
            wb.reshape(wb.shape[0], ch, 4), jnp.float32)
    return jax.lax.bitcast_convert_type(
        wb.reshape(wb.shape[0], ch, 2), jnp.bfloat16)


def slot_from_position(pos: jnp.ndarray, slot_cum: jnp.ndarray) -> jnp.ndarray:
    """Slot of each compacted position when row_idx is slot-grouped: slot s
    spans positions [cum[s-1], cum[s]) — a VPU compare-sum, no row gather."""
    return jnp.sum((pos[:, None] >= slot_cum[None, :]).astype(jnp.int32),
                   axis=1)


def table_lookup(idx: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """table[idx] for a SMALL table ([T<=1024, C]) as a one-hot f32 matmul.

    XLA's TPU gather prices a per-row dynamic lookup at the random-access
    tax (8.7 ns a row and more on the v5e: 0.127 s for one element gather
    over 14,680,064 rows; PERF.md, PR 28) even when the table is tiny. The
    one-hot [N, T] x [T, C] contraction costs by the WIDTH of the one-hot,
    T lanes produced and converted a row: at T = 256 and C = 6 it reads
    66 us a 65,536-row block, 16.5 ms over 14,680,064 rows with its convert
    = 1.12 ns a row, where a 25-wide one reads 0.88 ms (my chip runs;
    PERF.md, PR 31 and PR 33), so a lookup whose live keys are few belongs
    in :func:`keyed_lookup`. Exact for values with |v| < 2^24 (f32 integer
    range) — callers keep table entries inside that. Returns table.dtype.

    CAVEAT: rows of the table that are never selected still flow through
    the contraction with weight 0 — a non-finite entry there would poison
    the result (0 * Inf = NaN). Callers must keep garbage rows finite
    (grow_tree zeroes its scratch row before returning)."""
    T = table.shape[0]
    if T > 1024:          # one-hot width no longer trivial; gather wins back
        return table[idx]
    squeeze = table.ndim == 1
    t2 = (table[:, None] if squeeze else table).astype(jnp.float32)
    N = idx.shape[0]
    # bound the materialized [N_c, T] one-hot operand to ~64 MB f32 — at
    # bench scale (N=10.5M, T=256) an unchunked one-hot would be ~10.7 GB
    n_chunk = max(256, (1 << 24) // T)

    def lookup_block(ib):
        onehot = (ib[:, None] == jnp.arange(T, dtype=ib.dtype)[None, :]
                  ).astype(jnp.float32)
        # HIGHEST precision: the f32 operand is decomposed into bf16
        # triples whose reconstruction is exact (3x8 mantissa bits >=
        # f32's 24), and the one-hot side is 0/1 — so the selected value
        # comes back BIT-EXACT.
        return jax.lax.dot_general(
            onehot, t2,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)

    if N <= n_chunk:
        out = lookup_block(idx)
    else:
        n_blocks = (N + n_chunk - 1) // n_chunk
        pad = n_blocks * n_chunk - N
        idx_p = jnp.pad(idx, (0, pad)).reshape(n_blocks, n_chunk)
        out = jax.lax.map(lookup_block, idx_p).reshape(-1, t2.shape[1])[:N]
    if jnp.issubdtype(table.dtype, jnp.integer):
        out = jnp.round(out)
    out = out.astype(table.dtype)
    return out[:, 0] if squeeze else out


def keyed_lookup(idx: jnp.ndarray, keys: jnp.ndarray,
                 table: jnp.ndarray) -> jnp.ndarray:
    """``table[k]`` for the one ``k`` with ``keys[k] == idx[n]``, zeros for
    an ``idx`` no key carries: :func:`table_lookup` for a table of which
    only ``S = len(keys)`` rows are live, at the cost of an S-wide one-hot
    in place of a T-wide one.

    ``keys`` [S] are distinct where they can match (an unused ordinal
    carries a key no ``idx`` holds, e.g. -1), so a row matches at most one
    ordinal and the f32 ``Precision.HIGHEST`` contraction returns that
    table row bit-exact for |v| < 2^24, as ``table_lookup`` argues. Not
    blocked: the [N, S] match is never materialised, the TPU's compiler
    fuses the compare into the dot's operand and the round and convert
    into its output (one fusion over [N, C]: 0.88 ms at 14,680,064 rows,
    S = 25 and C <= 8 on the v5e, 1.58 at C = 13, 1.40 in 65,536-row
    blocks; PERF.md, PR 33). Integer tables only; returns ``[N, C]`` of
    ``table.dtype``."""
    match = (idx[:, None] == keys[None, :]).astype(jnp.float32)      # [N, S]
    out = jax.lax.dot_general(
        match, table.astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)
    return jnp.round(out).astype(table.dtype)


def build_histograms(
    X: jnp.ndarray,          # [N, F] uint8/uint16 bin codes (N padded to chunk multiple)
    grad: jnp.ndarray,       # [N] f32 (bagging-masked)
    hess: jnp.ndarray,       # [N] f32 (bagging-masked)
    included: jnp.ndarray,   # [N] f32 0/1 bagging/padding mask (count channel)
    leaf_id: jnp.ndarray,    # [N] i32 current leaf of each row (padding rows masked)
    slot_of_leaf: jnp.ndarray,  # [L+1] i32 leaf -> histogram slot, -1 = not pending
    num_slots: int,
    num_bins_padded: int,
    chunk_rows: int,
    row_idx: jnp.ndarray = None,   # [N] i32: a COMPACTED pass. The rows
                                   # grouped by pending slot, ascending
                                   # within a slot (grower._rows_by_slot);
                                   # needs n_active, slot_counts and packed
    n_active: jnp.ndarray = None,  # i32 count of valid row_idx entries
    exact: bool = False,           # weight mode: bf16 hi/lo channel pairs
                                   # (~f32 sums) | f32 columns at HIGHEST
    slot_counts: jnp.ndarray = None,  # [S] i32 rows per slot of row_idx: a
                                   # position's slot comes from the counts'
                                   # running sum, no per-row gather
    packed: jnp.ndarray = None,    # pack_rows(X, grad, hess, included): the
                                   # one array a compacted pass gathers its
                                   # rows from, built once a tree
    code_mode: str = None,         # packed-row code layout; None = by dtype
    compensated: bool = False,     # Kahan-compensate the chunk accumulation:
                                   # ~f64-accurate bin sums (the reference
                                   # accumulates bins in f64, bin.h:29-31)
                                   # without f64 hardware — config
                                   # tpu_hist_f64
    acc_init: jnp.ndarray = None,  # [F, B, S*ch] f32 accumulator carried in
                                   # from a PREVIOUS shard of the same wave
                                   # (out-of-core streaming, ops/stream.py):
                                   # chunk partials keep folding into it in
                                   # order, so a sharded pass is bit-identical
                                   # to one resident pass over the same rows
    comp_init: jnp.ndarray = None, # Kahan carry matching acc_init
    raw_output: bool = False,      # return the raw (acc, comp) fold state
                                   # instead of the finalized histogram —
                                   # streaming callers finalize once per wave
                                   # via finalize_histograms
    one_leaf: Optional[OneLeafForm] = None,  # the wave's pending leaves number
                                   # ONE (the caller's promise: one leaf, in
                                   # slot 0): build in the one-leaf form of
                                   # the chunk matmul (one_leaf_form of the
                                   # shapes); bf16 hi/lo weights only
) -> jnp.ndarray:
    """Returns hist [num_slots, F, num_bins_padded, 3] f32 (sum_g, sum_h, count).

    With (row_idx, n_active, slot_counts, packed) the pass is
    *row-compacted*: only ceil(n_active/chunk_rows) chunks run (a
    dynamic-trip-count while_loop), each gathering its packed rows through
    a slice of row_idx — the analog of the reference
    histogramming only the smaller leaf's rows
    (serial_tree_learner.cpp:354-362) instead of a full-data pass per wave.

    With ``acc_init``/``raw_output`` the pass is one *shard leg* of a
    streamed wave (tpu_residency=stream): the accumulator threads through
    every shard in row order — the identical chunk-partial add sequence the
    resident pass produces — and ``finalize_histograms`` combines once at
    the end of the wave.
    """
    n_rows, num_features = X.shape
    assert n_rows % chunk_rows == 0, (n_rows, chunk_rows)
    n_chunks = n_rows // chunk_rows
    ch = num_channels(exact)
    compact = row_idx is not None
    if one_leaf is not None:
        assert not (exact or compensated or raw_output) and acc_init is None, \
            "the one-leaf form builds bf16 hi/lo histograms of a resident pass"
        return _build_one_leaf(
            X, grad, hess, included, leaf_id, slot_of_leaf, one_leaf,
            num_slots, num_bins_padded, chunk_rows, row_idx, n_active, packed,
            code_mode)
    iota_bins = jnp.arange(num_bins_padded, dtype=jnp.int32)[None, None, :]
    iota_slots = jnp.arange(num_slots, dtype=jnp.int32)[None, :]
    iota_chunk = jnp.arange(chunk_rows, dtype=jnp.int32)
    if compact:
        assert slot_counts is not None and packed is not None, \
            "a compacted pass reads a slot-grouped row_idx: slot_counts, packed"
        slot_cum = jnp.cumsum(slot_counts)
        if code_mode is None:
            code_mode = default_code_mode(X.dtype)
        ncb = code_bytes_total(num_features, code_mode)

    def chunk_part(i):
        sl = jax.lax.dynamic_slice_in_dim
        if compact:
            # the compacted pass's row gathers, named apart from the
            # matmul they feed
            with jax.named_scope("wave.hist.compact.gather"):
                pos = i * chunk_rows + iota_chunk
                valid = pos < n_active
                idx = sl(row_idx, i * chunk_rows, chunk_rows)
                raw = slot_from_position(pos, slot_cum)
                pk = jnp.take(packed, idx, axis=0)                    # [R, Wb] u8
                xc = unpack_codes(pk[:, :ncb], num_features, code_mode)
                w = unpack_weights(pk[:, ncb:], ch, f32=exact)         # [R, ch]
                slot = jnp.where(valid, raw, -1)                       # [R]
        else:
            xc = sl(X, i * chunk_rows, chunk_rows)
            gc = sl(grad, i * chunk_rows, chunk_rows)
            hc = sl(hess, i * chunk_rows, chunk_rows)
            mc = sl(included, i * chunk_rows, chunk_rows)
            lc = sl(leaf_id, i * chunk_rows, chunk_rows)
            slot = table_lookup(lc, slot_of_leaf)                  # [R]
            w = weight_channels(gc, hc, mc, exact)                 # [R, ch]

        slot_onehot = (slot[:, None] == iota_slots)               # [R, S] bool
        rhs = (slot_onehot[:, :, None].astype(w.dtype) * w[:, None, :]
               ).reshape(chunk_rows, num_slots * ch)              # [R, S*ch]

        onehot = (xc.astype(jnp.int32)[:, :, None] == iota_bins
                  ).astype(w.dtype)                               # [R, F, B]
        part = jax.lax.dot_general(
            onehot, rhs,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            # exact mode: HIGHEST decomposes each f32 operand into bf16
            # triples, so every one-hot x weight product is EXACT (the
            # one-hot side is 0/1); bf16 hi/lo uses the default fast path
            precision=(jax.lax.Precision.HIGHEST if exact else None),
        )                                                         # [F, B, S*ch]
        return part

    acc0 = (acc_init if acc_init is not None else
            jnp.zeros((num_features, num_bins_padded, num_slots * ch),
                      jnp.float32))
    if compensated:
        # Kahan two-sum across chunk partials: the lost low-order bits of
        # every f32 add are carried forward, so the accumulated bin sums are
        # ~f64-accurate — the numerical effect of the reference's double
        # HistogramBinEntry sums (bin.h:29-31) on f32-native hardware. XLA
        # does not reassociate float arithmetic, so (t - acc) - y survives.
        def accumulate(carry, i):
            acc, comp = carry
            y = chunk_part(i) - comp
            t = acc + y
            return t, (t - acc) - y
    else:
        def accumulate(carry, i):
            acc, comp = carry
            return acc + chunk_part(i), comp
    if comp_init is not None:
        comp0 = comp_init
    else:
        comp0 = jnp.zeros_like(acc0) if compensated \
            else jnp.zeros((), jnp.float32)
    # "hist.kernel": the name a device trace finds the kernel's operations
    # by, whichever wave phase (or streamed shard leg) runs the pass
    with jax.named_scope("hist.kernel"):
        if compact:
            n_chunks_active = jnp.minimum(
                (n_active + chunk_rows - 1) // chunk_rows, n_chunks)

            def while_body(carry):
                i, acc, comp = carry
                acc, comp = accumulate((acc, comp), i)
                return i + 1, acc, comp

            _, acc, comp = jax.lax.while_loop(
                lambda c: c[0] < n_chunks_active, while_body,
                (jnp.asarray(0, n_chunks_active.dtype), acc0, comp0))
        else:
            (acc, comp), _ = jax.lax.scan(
                lambda c, i: (accumulate(c, i), ()), (acc0, comp0),
                jnp.arange(n_chunks))

    if raw_output:
        return acc, comp
    return finalize_histograms(acc, num_slots, exact)


def _build_one_leaf(X, grad, hess, included, leaf_id, slot_of_leaf,
                    form: OneLeafForm, num_slots: int, num_bins_padded: int,
                    chunk_rows: int, row_idx, n_active, packed, code_mode
                    ) -> jnp.ndarray:
    """``build_histograms`` for a wave that holds ONE pending leaf, in slot
    0: the same chunking, packed-row gather and scopes, its own chunk body
    (the one-leaf kernel on codes and weights laid feature-major) and its
    own ``form.acc_shape`` f32 accumulator, finalized into slot 0 of the
    [S, F, B, 3] result. Streamed: no slot lookup and no slot one-hot, a
    row is live where its leaf is the pending one (padding and out-of-sample
    rows carry weight 0). Compacted: the first ``n_active`` entries of
    ``row_idx`` are the leaf's rows, no ``slot_from_position``."""
    from .pallas_histogram import hist_one_leaf_chunk
    n_rows, num_features = X.shape
    n_chunks = n_rows // chunk_rows
    compact = row_idx is not None
    pad_f = form.features_padded - num_features
    iota_chunk = jnp.arange(chunk_rows, dtype=jnp.int32)
    sl = jax.lax.dynamic_slice_in_dim
    if compact:
        assert packed is not None, "a compacted pass reads the packed rows"
        if code_mode is None:
            code_mode = default_code_mode(X.dtype)
        ncb = code_bytes_total(num_features, code_mode)
    else:
        leaf = jnp.argmax(slot_of_leaf == 0).astype(leaf_id.dtype)

    def chunk_part(i):
        if compact:
            with jax.named_scope("wave.hist.compact.gather"):
                valid = i * chunk_rows + iota_chunk < n_active
                idx = sl(row_idx, i * chunk_rows, chunk_rows)
                pk = jnp.take(packed, idx, axis=0)                    # [R, Wb] u8
                xc = unpack_codes(pk[:, :ncb], num_features, code_mode)
                w = unpack_weights(pk[:, ncb:], NUM_CHANNELS)          # [R, 5]
                w = jnp.where(valid[:, None], w.astype(jnp.float32), 0.0)
            wt = jnp.pad(w, ((0, 0), (0, 8 - NUM_CHANNELS))).T         # [8, R]
        else:
            xc = sl(X, i * chunk_rows, chunk_rows).astype(jnp.int32)
            wt = _weight_rows(sl(grad, i * chunk_rows, chunk_rows),
                              sl(hess, i * chunk_rows, chunk_rows),
                              sl(included, i * chunk_rows, chunk_rows),
                              sl(leaf_id, i * chunk_rows, chunk_rows) == leaf)
        xt = jnp.pad(xc, ((0, 0), (0, pad_f))).T                      # [Fp, R]
        return hist_one_leaf_chunk(xt, wt, form)

    acc0 = jnp.zeros(form.acc_shape, jnp.float32)
    with jax.named_scope("hist.kernel"):
        if compact:
            n_chunks_active = jnp.minimum(
                (n_active + chunk_rows - 1) // chunk_rows, n_chunks)
            _, acc = jax.lax.while_loop(
                lambda c: c[0] < n_chunks_active,
                lambda c: (c[0] + 1, c[1] + chunk_part(c[0])),
                (jnp.asarray(0, n_chunks_active.dtype), acc0))
        else:
            acc, _ = jax.lax.scan(lambda a, i: (a + chunk_part(i), ()), acc0,
                                  jnp.arange(n_chunks))
    return finalize_one_leaf(acc, form, num_features, num_bins_padded,
                             num_slots)


def finalize_one_leaf(acc: jnp.ndarray, form: OneLeafForm, num_features: int,
                      num_bins_padded: int, num_slots: int) -> jnp.ndarray:
    """The one-leaf accumulator [groups, G*bins_hi, 128] -> [S, F, B, 3]
    with the leaf's histogram in slot 0 and zeros elsewhere: the diagonal
    feature blocks of each group's product, its columns ordered (feature of
    the group, channel, lo)."""
    G, Bh, Bl = form.group, form.bins_hi, form.bins_lo
    a = acc[:, :, :G * NUM_CHANNELS * Bl].reshape(
        form.groups, G, Bh, G, NUM_CHANNELS, Bl)
    d = jnp.stack([a[:, g, :, g] for g in range(G)], axis=1)  # [ng,G,Bh,ch,Bl]
    d = jnp.transpose(d, (0, 1, 2, 4, 3)).reshape(
        form.features_padded, Bh * Bl, NUM_CHANNELS)
    hist = combine_channels(d[:num_features, :num_bins_padded], exact=False)
    return jnp.zeros((num_slots,) + hist.shape, jnp.float32).at[0].set(hist)


def finalize_histograms(acc: jnp.ndarray, num_slots: int, exact: bool
                        ) -> jnp.ndarray:
    """[F, B, S*ch] f32 fold state -> [S, F, B, 3] (sum_g, sum_h, count).

    The combine/transpose tail of ``build_histograms``, split out so a
    streamed wave (which folds shard legs with ``raw_output=True``) runs it
    exactly once — the identical ops the resident pass ends with."""
    num_features, num_bins_padded, _ = acc.shape
    ch = acc.shape[-1] // num_slots
    acc = acc.reshape(num_features, num_bins_padded, num_slots, ch)
    acc = jnp.transpose(acc, (2, 0, 1, 3))                        # [S, F, B, ch]
    return combine_channels(acc, exact)                           # [S, F, B, 3]


def histogram_cost_report(n_rows: int, num_features: int,
                          num_bins_padded: int, num_slots: int,
                          chunk_rows: int, exact: bool = False, dtype=None,
                          site: str = None) -> dict:
    """Compile-time cost probe of the streaming histogram kernel at one
    shape class: lower+compile a standalone jitted ``build_histograms`` on
    zero inputs (values never affect the HLO) and publish the normalized
    FLOPs/bytes/HBM report through observability/costs.py. This is the
    kernel's dispatch-site cost leg — in production the kernel is fused
    into the train step, so its isolated cost is only observable here
    (golden-pinned in tests/test_costs.py). Explicit call = intent: runs
    regardless of the ``costs.enabled()`` gate."""
    from ..observability import costs as obs_costs
    dtype = jnp.uint8 if dtype is None else dtype
    n_rows = ((n_rows + chunk_rows - 1) // chunk_rows) * chunk_rows
    X = jnp.zeros((n_rows, num_features), dtype)
    zf = jnp.zeros(n_rows, jnp.float32)
    leaf_id = jnp.zeros(n_rows, jnp.int32)
    slot_of_leaf = jnp.zeros(num_slots + 1, jnp.int32)

    def run(X, g, h, inc, lid, sol):
        return build_histograms(X, g, h, inc, lid, sol, num_slots=num_slots,
                                num_bins_padded=num_bins_padded,
                                chunk_rows=chunk_rows, exact=exact)

    site = site or f"histogram.stream.s{num_slots}"
    dims = dict(rows=int(n_rows), features=int(num_features),
                bins=int(num_bins_padded), slots=int(num_slots),
                chunk_rows=int(chunk_rows))
    try:
        compiled = jax.jit(run).lower(X, zf, zf, zf, leaf_id,
                                      slot_of_leaf).compile()
        rep = obs_costs.report_from_compiled(compiled, site, dims)
    except Exception as e:                                   # noqa: BLE001
        rep = dict(dims, site=site, error=f"{type(e).__name__}: {e}"[:300])
    obs_costs.publish(rep)
    return rep


def root_sums(grad: jnp.ndarray, hess: jnp.ndarray, included: jnp.ndarray
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Total (sum_g, sum_h, count) over included rows — root LeafSplits init
    (reference: src/treelearner/leaf_splits.hpp Init)."""
    return (jnp.sum(grad, dtype=jnp.float32),
            jnp.sum(hess, dtype=jnp.float32),
            jnp.sum(included, dtype=jnp.float32))
