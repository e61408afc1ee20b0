"""Operations and bytes a histogram GBDT cannot avoid, from shapes alone.

The floor is one full root pass per tree in one-hot matmul form: every row's
code in every feature is compared against every bin and multiplied into the
(gradient, hessian, count) channels. Whatever builds the histograms, each
tree needs its root histogram over all rows, so the seconds this takes at the
chip's peak are a lower bound on the seconds per tree, and a share computed
from it cannot pass 100%.
"""


def root_pass_flops(rows: int, features: int, max_bin: int) -> float:
    # [rows, F*(max_bin+1)] one-hot x [rows, 3] weights, 2 FLOP per MAC
    return 2.0 * rows * features * (max_bin + 1) * 3


def root_pass_bytes(rows: int, features: int) -> float:
    # one u8 code per cell, plus f32 gradient and hessian per row
    return float(rows) * (features + 8)


def root_pass_floor_s(rows: int, features: int, max_bin: int, peaks: dict):
    """(seconds, "flops"|"bytes"): the least time one root pass takes on a
    chip with these peaks, and which peak bounds it."""
    tf = root_pass_flops(rows, features, max_bin) / peaks["flops_bf16"]
    tb = root_pass_bytes(rows, features) / peaks["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
