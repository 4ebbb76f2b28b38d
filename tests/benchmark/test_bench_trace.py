"""The reduction from a trace to numbers, on a small recorded trace with
answers worked by hand, and the peaks table with the kernel's bytes and ops."""

import json
import os

import pytest

from benchmark import peaks, tracered

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def small():
    with open(os.path.join(DATA, "trace_small.json")) as f:
        return json.load(f)


def test_busy_idle_and_window(small):
    r = tracered.reduce(small["trace"])
    want = small["answers"]
    assert r["window_s"] == pytest.approx(want["window_s"])
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    assert r["idle_share"] == pytest.approx(want["idle_share"])


def test_kernel_time_and_top_ops(small):
    r = tracered.reduce(small["trace"])
    want = small["answers"]
    assert r["kernel_events"] == want["kernel_events"]
    assert r["kernel_s"] == pytest.approx(want["kernel_s"])
    assert r["breakdown"]["device_ops"][0][0] == want["top_op"]
    assert r["spans"]["pipeline_feed"] == [2, pytest.approx(want["feed_s"])]


def test_gaps_go_to_the_innermost_span_covering_them(small):
    r = tracered.reduce(small["trace"])
    gaps = dict(r["breakdown"]["idle_gaps"])
    for name, seconds in small["answers"]["idle_gaps"].items():
        assert gaps[name] == pytest.approx(seconds), name
    assert sum(gaps.values()) == pytest.approx(
        r["window_s"] - r["busy_s"]
    )


def test_short_names():
    hlo = ('%pallas_call.8 = (s32[8,64]{1,0}) custom-call(s32[8,64]{1,0} %x), '
           'custom_call_target="tpu_custom_call", operand_layout={}')
    assert tracered.short_name(hlo) == "tpu_custom_call:pallas_call.8"
    assert tracered.short_name("%fusion.3 = s32[8]{0} fusion(%a)") == "fusion.3"
    assert tracered.short_name("while.6") == "while.6"


def test_recorded_chip_trace_reduces_to_the_numbers_recorded_with_it():
    path = os.path.join(DATA, "trace_recorded.json")
    with open(path) as f:
        doc = json.load(f)
    r = tracered.reduce(doc["trace"])
    for key, value in doc["answers"].items():
        assert r[key] == pytest.approx(value), key
    assert 0.0 < r["idle_share"] < 1.0 and r["kernel_events"] > 0


def test_kernel_bytes_and_ops_at_one_geometry_by_hand():
    # 8 rows, 8 deep, cap 64, 16 fill records, int32:
    # books in and out 2 * (10*8*64 + 3*8) * 4 = 41,152 bytes;
    # per op 8*8 * (8 + 5*16 + 8) * 4 = 24,576 bytes; ops 8*8 * 2*64 * 12
    assert peaks.kernel_cost(8, 8, 64, 16) == (41_152 + 24_576, 98_304)
    seconds, bound = peaks.kernel_min_seconds("TPU v5 lite", 8, 8, 64, 16)
    assert bound == "bytes"
    assert seconds == pytest.approx(65_728 / 819e9)


def test_deployment_geometry_is_bytes_bound():
    for rows, t, cap in ((10240, 32, 256), (2048, 32, 64), (8, 1024, 1024)):
        assert peaks.kernel_min_seconds("TPU v5 lite", rows, t, cap, 16)[1] \
            == "bytes"


def test_an_unknown_device_is_an_error_not_a_default():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v99")
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
