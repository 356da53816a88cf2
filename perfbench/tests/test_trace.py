"""The trace-to-metrics reduction on a small recorded trace (a 60 ms slice
of a kvcache_sessions run on a TPU v5 lite, PR 23) and on a hand-made one,
against numbers counted by hand; the kernel work function on hand-counted
shapes."""

import json
import os
import types

import pytest

from perfbench.lib import trace as tr
from perfbench.lib import work
from perfbench.readers import device_idle, encode_roofline

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture
def recorded():
    with open(os.path.join(DATA, "trace_slice_sessions.json")) as f:
        return json.load(f)


def test_recorded_slice(recorded):
    out = tr.reduce(recorded, window_s=0.06)
    # 46 operations on the XLA Ops line, none overlapping: 120 968 ns
    assert out["busy_s"] == pytest.approx(120968e-9, rel=1e-12)
    assert out["devices"] == 1 and out["window_s"] == 0.06
    # two calls of the fused encode+CRC program: 60 780 + 60 786 ns
    assert out["programs"] == {
        "jit__encode_device": [pytest.approx(121566e-9), 2]}
    assert out["device_ops"][0] == ["fusion.2", pytest.approx(55078e-9)]
    assert out["device_ops"][1][0] == "_gf2_matmul_3d.1"
    # three long gaps: [0, 1 093 584) ns from the first host mark to the
    # first operation, 24 111 221 ns between the two programs (from
    # 1 154 042), 18 679 697 ns from the last operation (25 325 827) to the
    # last mark's end. Each instant goes to the innermost mark covering it:
    # codec.encode_batch [730 330, 6 856 259) and [25 278 686, 31 125 366),
    # meta.batch_mkdirs [7 876 639, 10 299 168), else meta.batch_create
    gaps = dict(out["idle_gaps"])
    assert list(gaps) == ["meta.batch_create", "codec.encode_batch",
                          "meta.batch_mkdirs"]
    assert gaps["codec.encode_batch"] == pytest.approx(
        (363254 + 5702217 + 5799539) * 1e-9, rel=1e-6)
    assert gaps["meta.batch_mkdirs"] == pytest.approx(2422529e-9, rel=1e-6)
    assert gaps["meta.batch_create"] == pytest.approx(
        (730330 + 1020380 + 36411 + 14929684 + 12880158) * 1e-9, rel=1e-5)
    assert sum(gaps.values()) == pytest.approx(
        (1093584 + 24111221 + 18679697) * 1e-9, rel=1e-5)


def test_hand_made_trace():
    trace = {"planes": [
        {"name": "/device:TPU:0", "device": True, "lines": [
            {"name": "XLA Modules", "events": [["jit_step(12)", 0, 500]]},
            {"name": "XLA Ops", "events": [
                ["%a = f32[] x", 0, 100], ["%b = f32[] y", 50, 100],
                ["%a = f32[] x", 400, 100], ["%c", 1000, 10]]}]},
        {"name": "/host:CPU", "device": False, "lines": [
            {"name": "python3", "events": [
                ["pb:fio.read", 140, 300], ["pb:turn.load", 100, 950]]}]}]}
    out = tr.reduce(trace, window_s=2e-6)
    # [0,150) u [400,500) u [1000,1010) = 260 ns
    assert out["busy_s"] == pytest.approx(260e-9)
    assert out["device_ops"][0] == ["a", pytest.approx(200e-9)]
    assert out["programs"] == {"jit_step": [pytest.approx(500e-9), 1]}
    gaps = dict(out["idle_gaps"])
    # [150,400) is 250 ns, inside fio.read (the inner mark) and turn.load;
    # [500,1000) is 500 ns and [1010,1050), up to the last mark's end, 40:
    # both covered only by turn.load
    assert gaps == {"fio.read": pytest.approx(250e-9),
                    "turn.load": pytest.approx(540e-9)}
    run = types.SimpleNamespace(trace_data=out)
    assert device_idle.read(run, {}) == pytest.approx(100 * (1 - 260 / 2000))


def test_readers_find_nothing_rather_than_zero():
    run = types.SimpleNamespace(trace_data=None, codec_calls=[],
                                window=(0.0, 1.0), counters={},
                                device={"kind": "TPU v5 lite"})
    assert device_idle.read(run, {}) is None
    assert encode_roofline.read(run, {"program": "encode_device"}) is None
    run.trace_data = {"programs": {}, "window_s": 1.0, "busy_s": 0.0,
                      "devices": 0}
    assert device_idle.read(run, {}) is None
    assert encode_roofline.read(run, {"program": "encode_device"}) is None


def test_encode_work_hand_counted(recorded):
    # one RS(12,4) stripe of 87 552-byte shards: 12 shards in, 16 out,
    # 16 CRCs of 4 bytes; 2*64*4*12 = 6144 integer ops a byte column
    w = work.encode_work(1, 12, 4, 87552)
    assert w == {"bytes": 12 * 87552 + 16 * 87552 + 64,
                 "int8_ops": 6144 * 87552}
    peaks = work.peaks_of("TPU v5 lite")
    sec, bound = work.least_seconds(w, peaks)
    assert bound == "memory"
    assert sec == pytest.approx(2451520 / 819e9)
    with pytest.raises(KeyError):
        work.peaks_of("TPU v9 imaginary")
    # the reader on the recorded slice: two B=1 calls against 121 566 ns
    out = tr.reduce(recorded, window_s=0.06)
    run = types.SimpleNamespace(
        trace_data=out, window=(10.0, 11.0), counters={},
        device={"kind": "TPU v5 lite"},
        codec_calls=[(10.1, 10.2, 1, 12, 4, 87552, False),
                     (10.3, 10.4, 1, 12, 4, 87552, False),
                     (9.0, 9.1, 1, 12, 4, 87552, False)])   # before window
    share = encode_roofline.read(run, {"program": "encode_device"})
    assert share == pytest.approx(100 * 2 * (2451520 / 819e9) / 121566e-9)
    assert 0 < share < 100 and run.counters["encode_roofline_bound"] == \
        "memory"
