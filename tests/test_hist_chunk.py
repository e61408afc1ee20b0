"""The histogram pass sized by the table's width (ops/histogram.py:
``hist_pass_shape``): rows a chunk as a static function of the shapes, with
``tpu_hist_chunk`` as the upper bound. The rule has to leave every narrow
table what it was, and a pass has to sum the same histogram whatever chunk
the rule hands it: exactly where the sums are exact (counts; integer weights
in the f32 mode), to rounding elsewhere.
"""
import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops import histogram
from lightgbm_tpu.ops.histogram import (build_histograms, hist_pass_shape,
                                        num_channels, pack_rows)

N, F, B, S = 4096, 96, 64, 6


def _table(seed=5, integer=False):
    rng = np.random.RandomState(seed)
    X = jnp.asarray(rng.randint(0, B, size=(N, F)), jnp.uint8)
    draw = (lambda: rng.randint(-8, 9, size=N)) if integer else \
        (lambda: rng.randn(N))
    g = jnp.asarray(draw(), jnp.float32)
    h = jnp.asarray(np.abs(draw()) + 1, jnp.float32)
    inc = jnp.asarray(rng.rand(N) < 0.9, jnp.float32)
    leaf_id = jnp.asarray(rng.randint(0, S + 2, size=N), jnp.int32)
    # leaves S and S + 1 are not pending: their rows fall out of the pass
    slot_of_leaf = jnp.where(jnp.arange(S + 3) < S, jnp.arange(S + 3), -1
                             ).astype(jnp.int32)
    return X, g * inc, h * inc, inc, leaf_id, slot_of_leaf


def _compact_args(X, g, h, inc, leaf_id, exact):
    """A slot-grouped row index over the pending rows and the packed rows,
    as the grower's compacted arm gets them."""
    lid = np.asarray(leaf_id)
    pending = np.flatnonzero(lid < S)
    order = pending[np.argsort(lid[pending], kind="stable")].astype(np.int32)
    row_idx = np.zeros(N, np.int32)
    row_idx[: len(order)] = order
    counts = np.bincount(lid[pending], minlength=S).astype(np.int32)
    return dict(row_idx=jnp.asarray(row_idx),
                n_active=jnp.asarray(len(order), jnp.int32),
                slot_counts=jnp.asarray(counts),
                packed=pack_rows(X, g, h, inc, exact)[0])


@pytest.mark.parametrize("exact,compensated", [(False, False), (True, False),
                                               (True, True)],
                         ids=["hilo", "f32", "f32-kahan"])
@pytest.mark.parametrize("arm", ["stream", "compact"])
def test_a_narrower_chunk_sums_the_same_histogram(arm, exact, compensated):
    """256 rows a chunk against 4,096 (one chunk): the counts to the unit,
    the f32 mode's sums of integer weights to the bit, g and h of real
    weights to the rounding of the hi/lo mode."""
    integer = exact
    X, g, h, inc, leaf_id, slot_of_leaf = _table(integer=integer)
    kw = dict(num_slots=S, num_bins_padded=B, exact=exact,
              compensated=compensated)
    if arm == "compact":
        kw.update(_compact_args(X, g, h, inc, leaf_id, exact))
    args = (X, g, h, inc, leaf_id, slot_of_leaf)
    one = np.asarray(build_histograms(*args, chunk_rows=N, **kw))
    narrow = np.asarray(build_histograms(*args, chunk_rows=256, **kw))
    assert one.shape == (S, F, B, 3) and np.abs(one).sum() > 0
    np.testing.assert_array_equal(one[..., 2], narrow[..., 2])
    if integer:
        np.testing.assert_array_equal(one, narrow)
    else:
        np.testing.assert_allclose(one, narrow, rtol=0, atol=2e-4)


@pytest.mark.parametrize("exact,compensated", [(False, False), (True, True)],
                         ids=["hilo", "f32-kahan"])
def test_shard_legs_carry_the_accumulator_at_a_narrow_chunk(exact, compensated):
    """Two streamed shard legs (``acc_init``/``raw_output``, ops/stream.py)
    against one resident pass over the same rows at the same chunk: the
    same chunk partials folded in the same order, so equal to the bit."""
    X, g, h, inc, leaf_id, slot_of_leaf = _table()
    kw = dict(num_slots=S, num_bins_padded=B, chunk_rows=256, exact=exact,
              compensated=compensated)
    whole, whole_comp = build_histograms(X, g, h, inc, leaf_id, slot_of_leaf,
                                         raw_output=True, **kw)
    acc = comp = None
    half = N // 2
    for lo in (0, half):
        sl = slice(lo, lo + half)
        acc, comp = build_histograms(
            X[sl], g[sl], h[sl], inc[sl], leaf_id[sl], slot_of_leaf,
            acc_init=acc, comp_init=comp if compensated else None,
            raw_output=True, **kw)
        assert acc.shape == (F, B, S * num_channels(exact))
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(acc))
    if compensated:
        np.testing.assert_array_equal(np.asarray(whole_comp),
                                      np.asarray(comp))


# ------------------------------------------------------------ the shape rule

@pytest.mark.parametrize("rows,features,bins,channel_bytes,max_chunk,want", [
    # 14,680,064 x 67 x 256 bins, bf16 channels: the values PR 28's step
    # was compiled with; and the same table under tpu_hist_f64
    (14_680_064, 67, 256, 2, 32768, (32768, "max_chunk")),
    (14_680_064, 67, 256, 4, 32768, (32768, "max_chunk")),
    # HIGGS (chip_smoke.py), and the widest table that keeps 32,768 rows
    (10_500_000, 28, 256, 2, 32768, (32768, "max_chunk")),
    (1_000_000, 256, 256, 2, 32768, (32768, "max_chunk")),
    # shorter than a chunk; tpu_hist_chunk below everything
    (3000, 8, 32, 2, 32768, (3072, "max_chunk")),
    (3000, 8, 32, 2, 256, (256, "max_chunk")),
    (400_000, 2000, 256, 2, 1024, (1024, "max_chunk")),
    # Epsilon: sized by the width
    (400_000, 2000, 256, 2, 32768, (4096, "width")),
    (400_000, 2000, 256, 4, 32768, (2048, "width")),
], ids=["criteo", "criteo-f64", "higgs", "256-columns", "short", "short-256",
        "epsilon-bounded", "epsilon", "epsilon-f64"])
def test_shape_rule_at_the_shapes_measured(rows, features, bins,
                                           channel_bytes, max_chunk, want):
    assert hist_pass_shape(rows, features, bins, channel_bytes,
                           max_chunk) == want


@pytest.mark.parametrize("max_chunk", [256, 4096, 32768])
def test_shape_rule_over_shapes(max_chunk):
    """Whatever the shape: the chunk is a multiple of 256 under its upper
    bound and divides the rows once they are padded to it (the booster pads
    to a multiple of the chunk), and the one-hot operand of a chunk is under
    the rule's bytes wherever 256 rows of it can be."""
    for rows, features, (bins, channel_bytes) in itertools.product(
            [1, 255, 256, 3000, 32768, 400_000, 14_680_064],
            [1, 7, 67, 257, 500, 2000, 20_000],
            [(16, 2), (64, 2), (256, 2), (256, 4), (4096, 2), (65536, 4)]):
        chunk, rule = hist_pass_shape(rows, features, bins, channel_bytes,
                                      max_chunk)
        where = (rows, features, bins, channel_bytes, rule)
        assert 256 <= chunk <= max_chunk and chunk % 256 == 0, where
        padded = -(-rows // chunk) * chunk
        assert padded % chunk == 0 and padded - rows < chunk, where
        row_bytes = features * bins * channel_bytes
        assert chunk * row_bytes <= max(histogram._ONEHOT_BYTES_A_CHUNK,
                                        256 * row_bytes), where
        assert (rule == "max_chunk") == (
            chunk == min(max_chunk, -(-rows // 256) * 256)), where
