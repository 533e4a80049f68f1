"""The reduction from device events to busy, idle, exposed-collective and
per-operation time: interval arithmetic on hand-made events, then the same
functions on a trace recorded on the chip."""

import gzip
import json
import os

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(os.path.dirname(HERE), "testdata", "trace_events.json.gz")


def test_union_and_subtract():
    merged = tr.union([(5, 9), (0, 3), (2, 4), (9, 10)])
    assert merged == [(0, 4), (5, 10)]
    assert tr.total(merged) == 9
    assert tr.subtract([(0, 10)], merged) == [(4, 5)]
    assert tr.subtract([(0, 4), (6, 12)], [(1, 2), (3, 7), (11, 20)]) == [
        (0, 1), (2, 3), (7, 11),
    ]


def test_op_family_and_collectives():
    assert tr.op_family("fusion.123") == "fusion"
    assert tr.op_family("all-reduce-start.7") == "all-reduce-start"
    assert tr.op_family("copy") == "copy"
    assert tr.is_collective("all-reduce.59")
    assert tr.is_collective("all-gather-done.3")
    assert tr.is_collective("collective-permute-start")
    assert not tr.is_collective("fusion.59")
    assert not tr.is_collective("all-reduce-scatter-fusion")


def test_short_names_from_hlo_text():
    fusion = (
        "%fusion.18 = bf16[24,512,1024]{2,1,0:T(8,128)(2,1)} fusion(bf16[24,512,"
        "30522]{1,2,0:T(8,128)(2,1)} %gte.1594), kind=kOutput, calls=%fused"
    )
    assert tr.short_name(fusion) == "fusion.kOutput.18"
    kernel = (
        "%transpose_jvp___.25 = bf16[24,512,3072]{2,1,0:T(8,128)(2,1)S(1)} "
        "custom-call(bf16[24,512,3072]{2,1,0} %x), custom_call_target="
        '"tpu_custom_call", operand_layout_constraints={}'
    )
    assert tr.short_name(kernel) == "pallas/transpose_jvp___.25"
    psum = (
        "%psum.7 = (bf16[4096,1024]{1,0:T(8,128)(2,1)}, bf16[1024]{0:T(1024)"
        "(128)(2,1)S(1)}) all-reduce(bf16[4096,1024]{1,0} %a, bf16[1024]{0} %b)"
        ", channel_id=3, replica_groups={{0,1,2,3}}, to_apply=%add"
    )
    assert tr.short_name(psum) == "all-reduce/psum.7"
    assert tr.is_collective("all-reduce/psum.7")
    assert tr.op_family("all-reduce/psum.7") == "all-reduce/psum"
    plain = "%all-reduce.320 = bf16[8]{0} all-reduce(bf16[8]{0} %a), to_apply=%add"
    assert tr.short_name(plain) == "all-reduce.320"
    assert tr.is_collective("async-collective-done.4")
    assert tr.short_name("%copy.3 = f32[2]{0} copy(f32[2]{0} %x)") == "copy.3"


def hand_made():
    us = 1_000
    # chip 0: compute 0-40, collective 30-60 (30-40 hidden), compute 70-90
    # chip 1: compute 0-50, collective 50-55, compute 55-100
    return {
        "devices": {
            0: {
                "ops": [
                    ("fusion.1", 0, 40 * us),
                    ("all-reduce.2", 30 * us, 30 * us),
                    ("fusion.3", 70 * us, 20 * us),
                ],
                "modules": [("jit__step(1)", 0, 90 * us)],
            },
            1: {
                "ops": [
                    ("fusion.1", 0, 50 * us),
                    ("all-reduce.2", 50 * us, 5 * us),
                    ("custom-call.9", 55 * us, 45 * us),
                ],
                "modules": [("jit__step(1)", 0, 100 * us)],
            },
        },
        "host": [
            ("fit_chunk", 0, 80 * us),
            ("between_chunks", 80 * us, 5 * us),
            ("fit_chunk", 85 * us, 15 * us),
        ],
    }


def test_reduce_hand_made_events():
    r = tr.reduce_events(hand_made())
    assert r["window_s"] == pytest.approx(100e-6)
    # chip 0 is busy 0-60 and 70-90: 80 of 100; chip 1 all 100
    assert r["busy_s"] == pytest.approx((80e-6 + 100e-6) / 2)
    assert r["idle_share_worst_chip"] == pytest.approx(0.20)
    # chip 0: collective 30-60 minus compute 0-40 = 20 exposed
    assert r["collective_exposed_share_worst_chip"] == pytest.approx(0.20)
    assert r["collective_s_worst_chip"] == pytest.approx(30e-6)
    assert r["family_s"]["fusion"] == pytest.approx(60e-6)
    assert r["breakdown"]["device_ops"][0] == ["fusion", pytest.approx(60e-6)]
    gaps = dict(r["breakdown"]["idle_gaps"])
    # 60-70 lies inside the first fit_chunk; 90-100 inside the second
    assert gaps == {"fit_chunk": pytest.approx(20e-6)}
    assert tr.family_seconds(r, r"^all-reduce") == pytest.approx(30e-6)
    assert tr.family_seconds(r, r"no-such-op") is None


def test_gap_between_chunks_is_labelled():
    events = hand_made()
    events["devices"] = {
        0: {"ops": [("fusion.1", 0, 79_000), ("fusion.2", 86_000, 14_000)],
            "modules": []}
    }
    r = tr.reduce_events(events)
    gaps = dict(r["breakdown"]["idle_gaps"])
    # the gap 79-86 us has its middle in between_chunks
    assert gaps["between_chunks"] == pytest.approx(7e-6)


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_events({"devices": {0: {"ops": [], "modules": []}}, "host": []})


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_chip_trace():
    with gzip.open(RECORDED, "rt") as f:
        recorded = json.load(f)
    events = {
        "devices": {
            int(chip): {k: [tuple(e) for e in v] for k, v in lines.items()}
            for chip, lines in recorded["events"]["devices"].items()
        },
        "host": [tuple(e) for e in recorded["events"]["host"]],
    }
    r = tr.reduce_events(events)
    expected = recorded["expected"]
    for key in ("window_s", "busy_s", "idle_share_worst_chip",
                "collective_exposed_share_worst_chip"):
        assert r[key] == pytest.approx(expected[key], rel=1e-9), key
    assert r["program_runs"] == expected["program_runs"]
    for pattern, seconds in expected["family_seconds"].items():
        assert tr.family_seconds(r, pattern) == pytest.approx(seconds, rel=1e-9)
    assert 0.0 < r["busy_s"] <= r["window_s"]
