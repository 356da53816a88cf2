"""The pieces the node-loss cell brought: the conductor's timeline on a
fake cluster's routing, the phase sampler, the storage service's pass
lines, the decode's work and its roofline reader on a trace of the
recorded shape, and the plain decode's round trip."""

import types

import numpy as np
import pytest

from perfbench.drivers import kv_rebuild
from perfbench.lib import reference as ref
from perfbench.lib import reference_decode as refdec
from perfbench.lib import trace as tr
from perfbench.lib import work, work_decode
from perfbench.readers import counter, decode_roofline

S, U, O = "SERVING", "UPTODATE", "OFFLINE"


def look(mine, rest=12):
    """(mine, everyone) as Driver.states gives them: the lost node's four
    targets and the twelve others, which stay SERVING / UPTODATE."""
    return mine, mine + [(S, U)] * rest


def test_the_timeline_stamps_each_event_once_and_in_order():
    tl = kv_rebuild.Timeline()
    tl.observe(1.0, *look([(S, U)] * 4))
    assert tl.at == {}                       # nothing before the kill
    tl.stamp("t_kill", 6.0)
    tl.observe(6.1, *look([(S, U)] * 4))     # mgmtd has not noticed
    tl.observe(8.0, *look([(O, O)] * 3 + [(S, U)]))
    assert "t_offline" not in tl.at          # all four, not some
    tl.observe(10.3, *look([(O, O)] * 4))
    assert tl.get("t_offline") == 10.3 and "t_recovered" not in tl.at
    tl.observe(18.0, *look([("SYNCING", "ONLINE")] + [("WAITING", "ONLINE")] * 3))
    tl.observe(19.0, *look([(S, U)] + [("SYNCING", "ONLINE")] + [("WAITING", "ONLINE")] * 2))
    assert tl.get("t_syncing") == 18.0       # the first of them
    tl.observe(30.0, *look([(S, U)] * 3 + [(S, "ONLINE")]))
    assert "t_recovered" not in tl.at        # SERVING and up to date
    tl.observe(31.0, *look([(S, U)] * 4))
    tl.observe(32.0, *look([(S, U)] * 4))
    assert tl.get("t_recovered") == 31.0
    assert tl.between("t_kill", "t_offline") == pytest.approx(4.3)
    assert tl.between("t_syncing", "t_recovered") == 13.0
    assert tl.between("t_kill", "t_nothing") is None


def test_a_chain_that_looks_whole_before_the_loss_is_not_a_recovery():
    tl = kv_rebuild.Timeline()
    tl.stamp("t_kill", 1.0)
    for t in (1.1, 2.0, 3.0):
        tl.observe(t, *look([(S, U)] * 4))
    assert "t_recovered" not in tl.at and "t_offline" not in tl.at


def test_puts_are_sorted_by_when_they_were_acknowledged():
    assert kv_rebuild.phase_of(5.9, 6.0, 18.0) == "before"
    assert kv_rebuild.phase_of(6.0, 6.0, 18.0) == "outage"
    assert kv_rebuild.phase_of(17.9, 6.0, 18.0) == "outage"
    assert kv_rebuild.phase_of(18.0, 6.0, 18.0) == "rebuild"
    assert kv_rebuild.phase_of(40.0, 6.0, None) == "outage"
    assert kv_rebuild.phase_of(40.0, None, None) == "before"


def test_the_sampler_takes_a_share_of_each_phase():
    rng = np.random.default_rng(3)
    turns = {"before": list(range(0, 50)), "outage": [60, 61],
             "rebuild": list(range(100, 140))}
    got = kv_rebuild.sample_by_phase(turns, 11, rng)
    assert len(got) == 11 + 2 + 11 and len(set(got)) == len(got)
    assert sum(t < 50 for t in got) == 11 and {60, 61} <= set(got)
    assert sum(t >= 100 for t in got) == 11
    assert kv_rebuild.sample_by_phase({}, 4, rng) == []


def test_the_pass_lines_are_read_from_a_storage_log():
    log = "\n".join([
        "2026-10-03T17:01:06 [WARN ] MainThread: node 101 heartbeat",
        "ec.rebuild target=8 stripes=1383 installed=1383 installed_bytes=0 "
        "read_bytes=840000000 seconds=9.100 done=1",
        "ec.rebuild target=4 stripes=1390 installed=1388 "
        "installed_bytes=121522176 read_bytes=850000000 seconds=8.500 done=0",
        "ec.rebuild was here", ""])
    rows = kv_rebuild.parse_passes(log)
    assert [r["target"] for r in rows] == [8, 4]
    assert rows[1]["installed_bytes"] == 121522176 and rows[1]["done"] == 0
    assert rows[0]["seconds"] == 9.1 and rows[0]["stripes"] == 1383
    run = types.SimpleNamespace(counters={"rb_read_per_rebuilt": 13.9})
    assert counter.read(run, {"key": "rb_read_per_rebuilt"}) == 13.9
    assert counter.read(run, {"key": "rb_rebuild_s"}) is None


def test_decode_work_hand_counted():
    # one RS(12,4) stripe, one shard of 87 552 bytes lost: 12 shards in,
    # 1 out; an (8 x 96) bit matrix: 2*64*1*12 = 1536 integer ops a column
    w = work_decode.decode_work(1, 12, 1, 87552)
    assert w == {"bytes": 13 * 87552, "int8_ops": 1536 * 87552}
    sec, bound = work.least_seconds(w, work.peaks_of("TPU v5 lite"))
    assert bound == "memory" and sec == pytest.approx(13 * 87552 / 819e9)
    # a node's four from one read: 4 lost, 64 stripes
    w = work_decode.decode_work(64, 12, 4, 87552)
    assert w["bytes"] == 64 * 16 * 87552
    assert w["int8_ops"] == 2 * 64 * 4 * 12 * 64 * 87552


def decode_trace() -> dict:
    """A trace of the recorded shape (tests/data/trace_slice_sessions.json):
    three runs of the decode program, 30 us each, beside one encode."""
    ops = [["%_gf2_matmul_3d.1 = u8[1,1,90112]{2,1,0} custom-call(...)",
            t + 2000.0, 21000.0] for t in (1e6, 2e6, 3e6)]
    return {"planes": [
        {"name": "/device:TPU:0", "device": True, "lines": [
            {"name": "XLA Modules", "events": [
                ["jit__decode_device(123)", 1e6, 30000.0],
                ["jit__decode_device(123)", 2e6, 30000.0],
                ["jit__decode_device(123)", 3e6, 30000.0],
                ["jit__encode_device(7)", 4e6, 60000.0]]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "device": False, "lines": [
            {"name": "python3", "events": [
                ["pb:codec.reconstruct_batch", 0.9e6, 2.4e6]]}]}]}


def test_the_decode_roofline_on_a_trace():
    out = tr.reduce(decode_trace(), window_s=0.01)
    assert out["programs"]["jit__decode_device"] == [
        pytest.approx(90e-6), 3]
    call = (10.1, 10.2, 1, 12, 1, 87552, False)
    run = types.SimpleNamespace(
        trace_data=out, window=(10.0, 11.0), counters={},
        device={"kind": "TPU v5 lite"},
        decode_calls=[call, call, call,
                      (9.0, 9.1, 1, 12, 1, 87552, False),     # before it
                      (10.5, 10.6, 1, 12, 1, 87552, True)])   # on the host
    share = decode_roofline.read(run, {"program": "decode_device"})
    assert share == pytest.approx(100 * 3 * (13 * 87552 / 819e9) / 90e-6)
    assert 0 < share < 100
    assert run.counters["decode_calls"] == 3
    assert run.counters["decode_roofline_bound"] == "memory"


def test_the_decode_roofline_finds_nothing_rather_than_zero():
    out = tr.reduce(decode_trace(), window_s=0.01)
    run = types.SimpleNamespace(trace_data=None, window=(0.0, 1.0),
                                counters={}, device={"kind": "TPU v5 lite"})
    args = {"program": "decode_device"}
    assert decode_roofline.read(run, args) is None      # no trace
    run.trace_data = out
    assert decode_roofline.read(run, args) is None      # an older harness
    run.decode_calls = []
    assert decode_roofline.read(run, args) is None      # no call
    run.decode_calls = [(0.5, 0.6, 1, 12, 1, 87552, False)]
    run.trace_data = dict(out, programs={
        "jit__encode_device": out["programs"]["jit__encode_device"]})
    assert decode_roofline.read(run, args) is None      # an older program
    run.trace_data = out
    assert decode_roofline.read(run, args) > 0


LOSSES = [[3, 7, 11, 15], [0], [12, 13, 14, 15], [0, 5, 13], [2, 9]]


@pytest.mark.parametrize("lost", LOSSES)
def test_the_plain_decode_gives_back_what_the_plain_encode_made(lost):
    k, m, s = 12, 4, 96
    data = np.random.default_rng([4] + lost).integers(
        0, 256, (k, s), dtype=np.uint8)
    shards = np.concatenate([data, ref.rs_parity(data, m)])
    present = [j for j in range(k + m) if j not in lost][:k]
    got = refdec.decode(present, shards[present], k, m, lost)
    assert (got == shards[lost]).all()
    # any k rows of the generator are independent, fewer are refused
    with pytest.raises(ValueError):
        refdec.decode(present[:-1], shards[present[:-1]], k, m, lost)
    with pytest.raises(ValueError):
        refdec.decode(present[:-1] + present[:1], shards[present], k, m, lost)


def test_the_plain_decode_imports_nothing_of_the_program():
    import ast
    import inspect

    for mod in (refdec, work_decode):
        tree = ast.parse(inspect.getsource(mod))
        names = [n.module or "" for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom)]
        names += [a.name for n in ast.walk(tree)
                  if isinstance(n, ast.Import) for a in n.names]
        assert not any("tpu3fs" in n for n in names), names
