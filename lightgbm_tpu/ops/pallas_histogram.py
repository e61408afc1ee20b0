"""Pallas TPU histogram kernel — the direct replacement for the reference's
OpenCL histogram kernels (src/treelearner/ocl/histogram256.cl:95-125
local-memory atomic sub-histograms).

Design (vs the XLA one-hot matmul in ops/histogram.py):

- The [S*ch, F*B] f32 accumulator lives in VMEM scratch for the whole pass
  (≈2.3MB at S=16, ch=5, F=28, B=256) — the analog of the OpenCL kernel's
  per-workgroup local-memory sub-histograms, but with NO atomics: one core
  owns the whole accumulator and the grid walks row chunks sequentially.
- Each grid step loads a row chunk's bin codes [R, F] (uint8 -> tiny DMA),
  builds the per-leaf-slot weight columns rhs [R, S*ch] and the per-feature
  one-hot [R, B] IN VMEM (never HBM), and feeds the MXU with
  [S*ch, R] x [R, B] contractions per feature. The one-hot generation (VPU)
  pipelines against the matmul (MXU).
- Row compaction composes as a *chunk-level skip*: rows gathered to a
  pending-prefix order by the caller, and chunks past ceil(n_active/R) skip
  their compute via @pl.when — a skipped chunk costs only its (tiny) DMA,
  so the pass needs no dynamic trip count and no scatter.
- Under EFB the compacted pass's slot layout is BUNDLE-space native: the
  caller hands bundled columns with `num_bins_padded` = the bundle-bin pad
  (grower `hist_bins`), so the VMEM accumulator is [S*ch, G*Bb] — smaller
  than feature space by the bundling win ratio — and the packed row bytes
  carry bundle codes. The kernel never sees original-feature space; the
  bundle-space split scan (ops/split_finder.per_feature_best_bundled)
  consumes its output as-is, so no unpack sits between kernel and scan.

Precision matches ops/histogram.py: bf16 hi+lo gradient/hessian channels
accumulated in f32 (~f32-exact; the reference GPU path used plain f32 and
accepted small deltas, docs/GPU-Performance.rst:131-133). Counts are exact
(bf16 1.0 * onehot accumulated in f32).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .histogram import (NUM_CHANNELS, ONE_LEAF_TRIP_GROUPS, OneLeafForm,
                        code_bytes, combine_channels, slot_from_position,
                        table_lookup, unpack_weights)

_INTERPRET = False   # flipped by tests on CPU


def _hist_kernel(n_active_ref,        # SMEM scalar prefetch: [1] i32
                 x_ref,               # [R, F*cb] u8 bin-code bytes (chunk)
                 slot_ref,            # [R, 1] i32 slot per row (-1 = masked)
                 w_ref,               # [R, ch] bf16 weight channels (chunk)
                 out_ref,             # [SC, F*B] f32 — doubles as the VMEM
                                      # accumulator (constant index_map keeps
                                      # the block resident across grid steps)
                 *, chunk_rows: int, num_bins: int, num_features: int,
                 num_slots: int, cb: int):
    i = pl.program_id(0)
    acc_ref = out_ref

    @pl.when(i == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # chunk-level skip: all rows of this chunk are past the active prefix
    @pl.when(i * chunk_rows < n_active_ref[0])
    def _compute():
        # slot-weight columns built IN VMEM (never round-tripped via HBM):
        # rhs[r, s*ch+c] = (slot[r]==s) * w[r, c]. The accumulator's row
        # count is SC padded up to the f32 sublane tile (8) — Mosaic
        # rejects a [125, ...] block (S=25 x ch=5, the default-slot
        # config) outright; padded columns map to slot id >= num_slots,
        # which no row carries, so they stay zero and the caller slices
        # them off.
        ch = w_ref.shape[1]
        sc_pad = acc_ref.shape[0]
        slot = slot_ref[:]                                 # [R, 1]
        iota_s = jax.lax.broadcasted_iota(
            jnp.int32, (chunk_rows, sc_pad), 1) // ch
        w_rep = jnp.tile(w_ref[:], (1, -(-sc_pad // ch)))[:, :sc_pad]
        rhs = (slot == iota_s).astype(jnp.bfloat16) * w_rep   # [R, SC_pad]

        # One feature per step: the one-hot is a BROADCAST compare of the
        # feature column [R, 1] against a bin iota [R, B] — one VPU op per
        # one-hot element. The earlier f-blocked form first materialized
        # [R, fb*B] i32 via jnp.repeat and compared against iota%B, i.e.
        # 3-4 VPU passes over the same elements; the one-hot build is the
        # VPU-bound part of this kernel, so the extra passes were the
        # pass-level gap vs the MXU floor. Per-feature [R, B] contractions keep the MXU busy at
        # B >= 128 (2 lane tiles at B=256).
        iota_b = jax.lax.broadcasted_iota(
            jnp.int32, (chunk_rows, num_bins), 1)
        for f in range(num_features):
            if cb == 1:
                xs = x_ref[:, f:f + 1].astype(jnp.int32)      # [R, 1]
            else:
                # little-endian byte pair, two contiguous 1-column slices
                # (a stride-2 lane slice is lowered as a gather Mosaic
                # fails to shape-check)
                xs = (x_ref[:, 2 * f:2 * f + 1].astype(jnp.int32)
                      | (x_ref[:, 2 * f + 1:2 * f + 2].astype(jnp.int32)
                         << 8))                               # [R, 1]
            onehot = (xs == iota_b).astype(jnp.bfloat16)      # [R, B]
            part = jax.lax.dot_general(
                rhs, onehot,
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # [SC_pad, B]
            sl = slice(f * num_bins, (f + 1) * num_bins)
            acc_ref[:, sl] += part


def hist_pallas(
    Xb8: jnp.ndarray,          # [N, F*cb] u8 bin-code bytes
    slot: jnp.ndarray,         # [N] i32 histogram slot per row, -1 = skip
    w: jnp.ndarray,            # [N, 5] bf16 hi/lo weight channels
    num_slots: int,
    num_bins: int,
    num_features: int,
    cb: int,                   # bytes per code (1 = uint8, 2 = uint16)
    chunk_rows: int = 512,
    n_active: Optional[jnp.ndarray] = None,   # i32: rows [0, n_active) matter
) -> jnp.ndarray:
    """Returns hist [S, F, B, 3] f32 (sum_g, sum_h, count).

    The caller may pre-gather rows into a pending prefix and pass
    ``n_active`` — chunks fully past it skip compute (cheap DMA only).
    """
    N, ncb = Xb8.shape
    ch = w.shape[1]
    assert ch == NUM_CHANNELS, ch
    SC = num_slots * ch
    # f32 sublane-tile alignment for the accumulator block (see the
    # kernel's rhs comment): 125 -> 128 at the default S=25 x ch=5
    SC_pad = -(-SC // 8) * 8
    assert N % chunk_rows == 0, (N, chunk_rows)
    if n_active is None:
        n_active = jnp.asarray(N, jnp.int32)

    n_chunks = N // chunk_rows

    kernel = functools.partial(
        _hist_kernel, chunk_rows=chunk_rows, num_bins=num_bins,
        num_features=num_features, num_slots=num_slots, cb=cb)

    # the scope and the kernel's own name are what a device trace finds the
    # Mosaic call by (the xla kernel carries the same scope, ops/histogram.py)
    with jax.named_scope("hist.kernel"):
        out = pl.pallas_call(
            kernel,
            name="hist_kernel",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(n_chunks,),
                in_specs=[
                    pl.BlockSpec((chunk_rows, ncb), lambda i, n: (i, 0)),
                    pl.BlockSpec((chunk_rows, 1), lambda i, n: (i, 0)),
                    pl.BlockSpec((chunk_rows, ch), lambda i, n: (i, 0)),
                ],
                out_specs=pl.BlockSpec(
                    (SC_pad, num_features * num_bins), lambda i, n: (0, 0)),
            ),
            out_shape=jax.ShapeDtypeStruct(
                (SC_pad, num_features * num_bins), jnp.float32),
            interpret=_INTERPRET,
        )(n_active.reshape(1), Xb8, slot.reshape(N, 1), w)

    acc = out[:SC].reshape(num_slots, ch, num_features, num_bins)
    acc = jnp.transpose(acc, (0, 2, 3, 1))                        # [S, F, B, ch]
    return combine_channels(acc, exact=False)                     # [S, F, B, 3]


def build_histograms_pallas(
    X: jnp.ndarray,
    grad: jnp.ndarray,
    hess: jnp.ndarray,
    included: jnp.ndarray,
    leaf_id: jnp.ndarray,
    slot_of_leaf: jnp.ndarray,
    num_slots: int,
    num_bins_padded: int,
    chunk_rows: int,
    row_idx: jnp.ndarray = None,       # [N] i32: a COMPACTED pass, the rows
                                       # grouped by pending slot; needs
                                       # n_active and slot_counts
    n_active: jnp.ndarray = None,
    slot_counts: jnp.ndarray = None,   # [S] i32 rows per slot of row_idx:
                                       # slots derive from position (no
                                       # leaf_id/slot_of_leaf row gathers)
    packed: jnp.ndarray = None,        # REQUIRED: pack_rows(X, grad, hess,
                                       # included, exact=False), built once a
                                       # tree; the kernel reads the code bytes
                                       # and the bf16 hi/lo weights from it
    max_rows: int = 0,                 # STATIC cap on n_active (0 = N). The
                                       # grower's adaptive cond guarantees
                                       # n_active < N/4 on this path, so the
                                       # kernel grid and gather buffers can
                                       # shrink 4x — skipped grid steps are
                                       # not free at a 10.5M-row full grid.
) -> jnp.ndarray:
    """ops.histogram.build_histograms backed by the Pallas kernel, bf16 hi/lo
    weights only (same semantics — the GPU_DEBUG_COMPARE analog lives in
    tests/test_pallas_hist.py). The two are called alike: ``grad``, ``hess``
    and ``included`` stay in the signature, but this kernel reads them from
    ``packed`` in every pass.

    With ``max_rows`` set, active rows beyond it are silently dropped — the
    caller must guarantee n_active <= max_rows."""
    N, F = X.shape
    cb = code_bytes(X.dtype)
    assert packed is not None, "the Pallas kernel reads the packed rows"
    ncb = F * cb
    if row_idx is not None:
        assert slot_counts is not None, \
            "a compacted pass reads a slot-grouped row_idx: slot_counts"
        # pending-rows gather, bounded to active chunks only — ONE random
        # row gather from the packed array per active row (vs four separate
        # X/g/h/inc gathers; a random HBM row access costs the same ~30 ns
        # regardless of row width). Gather granularity (32k rows) is
        # independent of the kernel grid step (512 rows). Rg must divide
        # the buffer length or the tail rows would silently never be
        # gathered.
        cap = N if max_rows in (0, None) else min(max_rows, N)
        R = min(chunk_rows, cap)
        cap = ((cap + R - 1) // R) * R
        Rg = min(32768, cap)
        while Rg > 1 and cap % Rg:
            Rg //= 2
        n_chunks_active = jnp.minimum((n_active + Rg - 1) // Rg, cap // Rg)
        iota_r = jnp.arange(Rg, dtype=jnp.int32)
        slot_cum = jnp.cumsum(slot_counts)

        def gather_chunk(c, bufs):
            pb, sb = bufs
            sl = c * Rg
            pos = sl + iota_r
            raw = slot_from_position(pos, slot_cum)
            idx = jax.lax.dynamic_slice_in_dim(row_idx, sl, Rg)
            chunk_slot = jnp.where(pos < n_active, raw, -1)
            upd = jax.lax.dynamic_update_slice_in_dim
            return (upd(pb, jnp.take(packed, idx, axis=0), sl, 0),
                    upd(sb, chunk_slot, sl, 0))

        bufs = (jnp.zeros((cap, packed.shape[1]), packed.dtype),
                jnp.full(cap, -1, jnp.int32))
        _, bufs = jax.lax.while_loop(
            lambda c: c[0] < n_chunks_active,
            lambda c: (c[0] + 1, gather_chunk(c[0], c[1])),
            (jnp.asarray(0, jnp.int32), bufs))
        packed, slot = bufs
        n_rows = cap
    else:
        slot = table_lookup(leaf_id, slot_of_leaf)
        n_active = None
        n_rows = N
    Xb8 = packed[:, :ncb]
    w = unpack_weights(packed[:, ncb:], NUM_CHANNELS)
    return hist_pallas(Xb8, slot, w, num_slots, num_bins_padded,
                       num_features=F, cb=cb,
                       chunk_rows=min(chunk_rows, n_rows),
                       n_active=n_active)


# ---- the one-leaf form (ops/histogram.one_leaf_form) ------------------------
# rows of a grid step's tile the kernel turns into one-hots at a time: the
# [bins_hi x G, 512] and [128, 512] operands of one matmul stay in vregs
_ONE_LEAF_SUB_ROWS = 512


def one_leaf_runs_on(platform: str) -> bool:
    """The one-leaf kernel is a Mosaic kernel: it exists for a TPU (and,
    interpreted, wherever the tests flip ``_INTERPRET``)."""
    return platform == "tpu" or _INTERPRET


def _one_leaf_kernel(x_ref,           # [block_groups*G, Rt] i32 bin codes,
                                      # feature-major: rows along the lanes
                     w_ref,           # [8, Rt] f32 weight channels as rows
                                      # (g_hi, g_lo, h_hi, h_lo, count, 0..)
                     out_ref,         # [block_groups, G*bins_hi, 128] f32:
                                      # the VMEM accumulator of this block of
                                      # feature groups over the row tiles
                     *, form: OneLeafForm):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    G, Bh, Bl = form.group, form.bins_hi, form.bins_lo
    lo_bits = Bl.bit_length() - 1
    cols = G * NUM_CHANNELS * Bl
    sub = min(_ONE_LEAF_SUB_ROWS, form.row_tile)
    iota_lo = jax.lax.broadcasted_iota(jnp.int32, (Bl, sub), 0)
    iota_hi = jax.lax.broadcasted_iota(jnp.int32, (Bh, sub), 0)

    def group_part(g, rows):
        """One feature group's [G*bins_hi, 128] product over ``sub`` rows."""
        w = w_ref[:, rows]                                     # [8, sub]
        lhs, rhs = [], []
        for j in range(G):
            # one feature's codes, broadcast down the sublanes: every
            # compare and select below is one op on a natural (8, 128)
            # f32 tile, no lane shuffle
            x = x_ref[pl.ds(g * G + j, 1), rows]               # [1, sub]
            lhs.append(jnp.where((x >> lo_bits) == iota_hi, 1.0, 0.0))
            is_lo = (x & (Bl - 1)) == iota_lo                   # [Bl, sub]
            rhs += [jnp.where(is_lo, w[c:c + 1, :], 0.0)
                    for c in range(NUM_CHANNELS)]
        if cols < 128:
            rhs.append(jnp.zeros((128 - cols, sub), jnp.float32))
        # rows on the lanes of BOTH operands: the q . k^T contraction
        return jax.lax.dot_general(
            jnp.concatenate(lhs, axis=0).astype(jnp.bfloat16),
            jnp.concatenate(rhs, axis=0).astype(jnp.bfloat16),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # [G*Bh, 128]

    # ONE_LEAF_TRIP_GROUPS groups a trip of a real loop: a Python loop over
    # all 23 groups x 4 row slices traced 8,000 operations an arm, 16 s of
    # every process's first dispatch on the chip's host (PERF.md, PR 37)
    trip = ONE_LEAF_TRIP_GROUPS
    assert form.block_groups % trip == 0, form

    def groups_body(t, carry):
        for u in range(trip):
            g = t * trip + u
            part = group_part(g, pl.ds(0, sub))
            for s in range(1, form.row_tile // sub):
                part += group_part(g, pl.ds(s * sub, sub))
            out_ref[g] += part
        return carry

    jax.lax.fori_loop(0, form.block_groups // trip, groups_body, 0)


def hist_one_leaf_chunk(xt: jnp.ndarray,     # [form.features_padded, R] i32
                        wt: jnp.ndarray,     # [8, R] f32, bf16-representable
                        form: OneLeafForm) -> jnp.ndarray:
    """One chunk of a ONE-LEAF wave's histogram pass: [form.groups,
    G*bins_hi, 128] f32 with, for feature group ``g``,
    ``out[g, j*bins_hi + hi, j'*40 + c*8 + lo]`` = the sum of channel ``c``
    over the chunk's rows whose feature ``g*G + j`` has the hi code and
    whose feature ``g*G + j'`` has the lo code; ``j == j'`` is the
    histogram (``ops/histogram.finalize_one_leaf`` takes it). Both one-hots
    are built in VMEM; the grid walks blocks of feature groups, and inside a
    block the chunk's row tiles, accumulating in the resident output block."""
    assert (xt.shape[0] == form.features_padded
            and xt.shape[1] % form.row_tile == 0), (xt.shape, form)
    return _one_leaf_call(xt, wt, form, _INTERPRET)


# jitted, so the kernel's body is traced once a process and not once for
# every arm and every loop's fixpoint that holds a call
@functools.partial(jax.jit, static_argnums=(2, 3))
def _one_leaf_call(xt, wt, form: OneLeafForm, interpret: bool):
    block = form.block_groups * form.group
    return pl.pallas_call(
        functools.partial(_one_leaf_kernel, form=form),
        name="hist_one_leaf",
        grid=(form.groups // form.block_groups,
              xt.shape[1] // form.row_tile),
        in_specs=[pl.BlockSpec((block, form.row_tile), lambda j, i: (j, i)),
                  pl.BlockSpec((8, form.row_tile), lambda j, i: (0, i))],
        out_specs=pl.BlockSpec((form.block_groups,) + form.acc_shape[1:],
                               lambda j, i: (j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(form.acc_shape, jnp.float32),
        interpret=interpret,
    )(xt, wt)
