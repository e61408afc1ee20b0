"""The trace reduction (lib/xplane.py) checked on traces whose answers are
known: a hand-made one, where every number can be worked out on paper, and a
small one recorded on the chip (benchmarks/data/trace_small.events.json.gz:
the first 0.12 s of a traced dispatch of criteo67-255 on a v5e, 2,238 whole
events), whose expected numbers were computed once by an independent numpy
sweep, not by the code under test.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_trace_reduction.py -q
"""
import json
import os

import pytest

from lib import xplane

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "data")


def hand_made() -> dict:
    # ns:  0        100       200       300       400       500       600
    # while.1 [0 ............................................ 500)
    #   fusion.1 (fusion/kOutput: the matmul) [10,110)
    #   while.2 [150 .................. 350)
    #     sort.7 [160,200)   gather.3 [220,300)
    #   fusion.9 (fusion/kLoop) [400,480)
    # copy.4 [520,560)        host: bench.traced [0,600) bench.block [500,600)
    dev = [["while.1", "while", 0, 500],
           ["fusion.1", "fusion/kOutput", 10, 100],
           ["while.2", "while", 150, 200],
           ["sort.7", "sort", 160, 40],
           ["gather.3", "gather", 220, 80],
           ["fusion.9", "fusion/kLoop", 400, 80],
           ["copy.4", "copy", 520, 40]]
    return {"devices": {"/device:TPU:0": dev},
            "host": [["bench.traced", 0, 600], ["bench.block", 500, 100]]}


def test_busy_is_the_union_of_leaf_operations():
    r = xplane.reduce_events(hand_made())
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(600e-9)
    # leaves: 100 + 40 + 80 + 80 + 40
    assert r["busy_s"] == pytest.approx(340e-9)


def test_time_is_counted_once_as_self_time_and_classed():
    r = xplane.reduce_events(hand_made())
    assert r["class_s"]["matmul"] == pytest.approx(100e-9)
    assert r["class_s"]["custom"] == 0.0
    # while.1 self = 500-100-200-80 = 120; while.2 self = 200-40-80 = 80;
    # sort 40, gather 80, loop fusion 80, copy 40
    assert r["class_s"]["other"] == pytest.approx(440e-9)
    assert sum(r["class_s"].values()) == pytest.approx(540e-9)     # 500 + 40
    assert r["class_events"] == {"matmul": 1, "custom": 0, "other": 6}
    assert r["n_events"] == 7
    assert r["ops"][0] == ["other:while:while", pytest.approx(200e-9)]


def test_gaps_are_named_by_what_encloses_them():
    r = xplane.reduce_events(hand_made())
    gaps = dict(r["gaps"])
    assert sum(gaps.values()) == pytest.approx(260e-9)             # 600 - 340
    # [480,520) has its middle at 500, where while.1 has ended, and [560,600)
    assert gaps["host:bench.block"] == pytest.approx(80e-9)
    # [0,10) [110,160) and [300,400): while.2 has ended at 350
    assert gaps["device:inside while"] == pytest.approx(180e-9)
    assert r["longest_gap_s"] == pytest.approx(100e-9)             # [300,400)


def test_classes_come_from_the_hlo_text():
    name, cat = xplane.parse_hlo(
        "%select_add_fusion.4 = f32[67,256,125]{2,1,0:T(8,128)S(1)} fusion(f32[67,256,125]"
        "{2,1,0:T(8,128)S(1)} %get-tuple-element.2779, s32[32768,67]{0,1:T(8,128)S(1)} %x), "
        "kind=kOutput, calls=%fused_computation.143.clone.clone")
    assert (name, cat) == ("select_add_fusion.4", "fusion/kOutput")
    assert xplane.classify(name, cat) == "matmul"
    # the compiler's own gather/scatter fusion is not a kernel
    name, cat = xplane.parse_hlo(
        "%fusion.547 = s32[12582912]{0:T(1024)S(1)} fusion(s32[12582912]{0:T(1024)S(1)} "
        "%custom-call.217, s32[12582912]{0:T(1024)} %g), kind=kCustom, calls=%fused_computation.92")
    assert (name, cat) == ("fusion.547", "fusion/kCustom")
    assert xplane.classify(name, cat) == "other"
    name, cat = xplane.parse_hlo(
        '%custom-call.3 = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} %p), '
        'custom_call_target="tpu_custom_call", backend_config={}')
    assert xplane.classify(name, cat) == "custom"
    name, cat = xplane.parse_hlo(
        '%custom-call.227 = f32[12582912]{0:T(1024)S(1)} custom-call(f32[3145728]{0} %a), '
        'custom_call_target="ConcatBitcast"')
    assert xplane.classify(name, cat) == "other"
    name, cat = xplane.parse_hlo(
        "%sort.14 = (s32[12582912]{0:T(1024)S(1)}, s32[12582912]{0:T(1024)}) sort(s32[12582912]"
        "{0:T(1024)} %a, s32[12582912]{0:T(1024)S(1)} %b), dimensions={0}, to_apply=%compare")
    assert (name, cat) == ("sort.14", "sort")
    assert xplane.classify("dot.5", "dot") == "matmul"
    assert xplane.parse_hlo("bench.traced") == ("bench.traced", "")


def test_recorded_trace_reduces_to_its_recorded_numbers():
    path = os.path.join(DATA, "trace_small.events.json.gz")
    with open(os.path.join(DATA, "trace_small.expected.json")) as f:
        want = json.load(f)
    r = xplane.reduce_events(xplane.load_events(path))
    assert r["n_events"] == want["n_events"]
    assert r["class_events"] == want["class_events"]
    for key in ("busy_s", "window_s"):
        assert r[key] == pytest.approx(want[key], rel=1e-9)
    for cls, seconds in want["class_s"].items():
        assert r["class_s"][cls] == pytest.approx(seconds, rel=1e-9)
    assert r["busy_s"] <= r["window_s"]
    assert sum(r["class_s"].values()) <= r["window_s"] * (1 + 1e-9)
