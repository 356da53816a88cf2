"""The span reader on a hand-written span list: each mode, the window's
edges, nothing to read, dropped rows, and a program with no sink."""

import json
import os
import types

import pytest

from perfbench.readers import span_ms

FIELDS = ("span_id", "parent_id", "op", "stage", "t_perf", "dur_us",
          "nbytes")


def row(sid, parent, op, stage, t_ms, dur_ms, nbytes=0):
    return (sid, parent, op, stage, t_ms / 1e3, dur_ms * 1e3, nbytes)


# two puts inside the window (10.0 s .. 11.0 s), one before, one after it.
# put A: 40 ms; a meta op 5 ms; two stripes' worth of RPC hops, the second
# pair overlapping; an encode of 3 ms with one dispatch of 2 stripes.
ROWS = [
    row("A", "", "kv.put", "", 10_000, 40, nbytes=2000),
    row("A1", "A", "meta.create", "", 10_001, 5),
    row("A1h", "A1", "rpc.client.4.3", "", 10_001.5, 4),
    row("A1s", "A1h", "rpc.client", "server_run", 10_002, 2),
    row("A2", "A", "fio.write", "", 10_007, 30, nbytes=2000),
    row("A2s", "A2", "client.ws", "stage", 10_008, 12),
    row("A2h1", "A2s", "rpc.client.ring", "", 10_008, 6),
    row("A2h1r", "A2h1", "rpc.client", "server_run", 10_009, 3),
    row("A2h1i", "A2h1", "rpc.client", "issue", 10_008, 1),
    row("A2h2", "A2s", "rpc.client.ring", "", 10_012, 6),   # overlaps h1 by 2
    row("A2h2r", "A2h2", "rpc.client", "server_run", 10_013, 4),
    row("A2e", "A2", "codec.encode", "", 10_021, 3, nbytes=4000),
    row("A2ed", "A2e", "codec.encode", "dispatch", 10_021, 1, nbytes=2),
    # put B: 20 ms, one hop of 10 ms, no meta
    row("B", "", "kv.put", "", 10_500, 20, nbytes=1000),
    row("B2", "B", "fio.write", "", 10_501, 18, nbytes=1000),
    row("B2h", "B2", "rpc.client.ring", "", 10_502, 10),
    row("B2hr", "B2h", "rpc.client", "server_run", 10_503, 8),
    # outside the window: started before it / after it
    row("C", "", "kv.put", "", 9_990, 500, nbytes=1000),
    row("Ch", "C", "rpc.client.ring", "", 9_995, 400),
    row("D", "", "kv.put", "", 11_001, 5, nbytes=1000),
    row("Dh", "D", "rpc.client.ring", "", 11_002, 1),
    # a nested root counts like an outermost one
    row("E", "", "outer", "", 10_700, 50),
    row("E1", "E", "kv.put", "", 10_710, 30, nbytes=1000),
    row("E1h", "E1", "rpc.client.ring", "", 10_711, 2),
]


def make_run(rows=ROWS, dropped=0):
    run = types.SimpleNamespace(window=(10.0, 11.0), counters={})
    run.span_index = span_ms.Index(rows, FIELDS) if not dropped else None
    return run


def read(run=None, **args):
    return span_ms.read(run or make_run(), {"root": "kv.put", **args})


def test_sum_adds_the_picked_spans_of_each_root_and_takes_the_median():
    # hops: A 4 + 6 + 6 = 16 ms, B 10 ms, E1 2 ms -> median 10
    assert read(mode="sum", pick=["rpc.client.*"], unit="ms") == \
        pytest.approx(10.0)
    assert read(mode="sum", pick=["rpc.client.*"], unit="us") == \
        pytest.approx(10_000.0)
    # stages are picked as op.stage, and an op pattern never takes a stage
    # row: A 2 + 3 + 4 = 9, B 8; E1 has no such stage and is left out
    assert read(mode="sum", stages=["rpc.client.server_run"], unit="ms") == \
        pytest.approx(8.5)
    assert read(mode="sum", stages=["rpc.client.server_run"], unit="s") == \
        pytest.approx(0.0085)


def test_under_keeps_only_what_lies_below_a_matching_span():
    # only the hops below fio.*: A 6 + 6, B 10, E1 none
    assert read(mode="sum", pick=["rpc.client.*"], under="fio.*",
                unit="ms") == pytest.approx(11.0)
    assert read(mode="sum", pick=["rpc.client.*"], under="meta.*",
                unit="ms") == pytest.approx(4.0)


def test_union_counts_overlapping_spans_once():
    # A's two ring hops overlap by 2 ms: 6 + 6 - 2 = 10; B 10
    assert read(mode="union", pick=["rpc.client.ring"], under="fio.*",
                unit="ms") == pytest.approx(10.0)


def test_self_is_the_duration_less_the_union_of_what_is_beneath():
    # no pick: less the direct children. A: 40 - (5 + 30) = 5; B: 20 - 18;
    # E1: 30 - 2 -> median 5
    assert read(mode="self", unit="ms") == pytest.approx(5.0)
    # with a pick: less the union of the picked spans, overlap once
    # A: 40 - (4 + 10) = 26; B: 10; E1: 28 -> median 26
    assert read(mode="self", pick=["rpc.client.*"], unit="ms") == \
        pytest.approx(26.0)
    # of: the self time of every fio.write beneath the root
    # A: 30 - 10 = 20; B: 18 - 10 = 8; E1 has none
    assert read(mode="self", of="fio.write", pick=["rpc.client.*"],
                unit="ms") == pytest.approx(14.0)
    # a leaf's self time is its duration
    assert span_ms.read(make_run(), {"root": "codec.encode", "mode": "self",
                                     "stages": ["x.y"], "unit": "ms"}) == \
        pytest.approx(3.0)


def test_count_and_count_over_the_root_s_bytes():
    assert read(mode="count", pick=["rpc.client.*"]) == pytest.approx(1.0)
    # A: 3 hops over 2000/1000 blocks = 1.5; B: 1; E1: 1
    assert read(mode="count", pick=["rpc.client.*"], per_bytes=1000) == \
        pytest.approx(1.0)
    assert span_ms.read(make_run(), {
        "root": "fio.write", "mode": "count", "pick": ["rpc.client.*"],
        "per_bytes": 1000}) == pytest.approx(1.0)   # A2: 2/2, B2: 1/1


def test_mean_nbytes_reads_a_count_kept_in_nbytes():
    assert span_ms.read(make_run(), {
        "root": "codec.encode", "mode": "mean_nbytes",
        "stages": ["codec.encode.dispatch"]}) == pytest.approx(2.0)


def test_the_window_s_edges():
    # C started before the window and D after it: neither is read, however
    # long; a root on the edge itself is
    run = make_run()
    run.window = (10.5, 10.5)
    assert read(run, mode="sum", pick=["rpc.client.*"], unit="ms") == \
        pytest.approx(10.0)           # B alone
    run = make_run()
    run.window = (9.0, 12.0)          # now all five
    assert read(run, mode="sum", pick=["rpc.client.*"], unit="ms") == \
        pytest.approx(10.0)           # 400, 16, 10, 2, 1
    assert read(run, mode="count", pick=["rpc.client.*"]) == 1.0


def test_nothing_to_read_is_none_never_zero():
    assert read(mode="sum", pick=["no.such.*"], unit="ms") is None
    assert span_ms.read(make_run(), {"root": "no.such", "mode": "sum",
                                     "pick": ["*"], "unit": "ms"}) is None
    run = make_run()
    run.window = (20.0, 21.0)
    assert read(run, mode="sum", pick=["rpc.client.*"], unit="ms") is None
    assert read(make_run(rows=[]), mode="count", pick=["*"]) is None


def test_dropped_rows_silence_every_metric(monkeypatch):
    from tpu3fs.analytics import spans

    tracer = spans.Tracer()
    ctx = spans.TraceContext("t", "s", profiled=True)
    tracer.finish_op(ctx, "kv.put", 1.0, 0.2, t_perf=10.5)
    monkeypatch.setattr(spans, "_TRACER", tracer)
    run = types.SimpleNamespace(window=(10.0, 11.0), counters={})
    assert read(run, mode="self", unit="ms") == pytest.approx(200.0)
    assert run.counters == {"spans_captured": 1}
    monkeypatch.setattr(tracer, "_captured_total", 5)   # four rows went
    run = types.SimpleNamespace(window=(10.0, 11.0), counters={})
    assert read(run, mode="self", unit="ms") is None
    assert read(run, mode="count", pick=["*"]) is None
    assert run.counters["spans_dropped"] == 4


def test_a_program_without_the_sink_reads_as_nothing(monkeypatch):
    """The parent of the PR that brought the sink: the reader returns
    nothing and does not raise."""
    from tpu3fs.analytics import spans

    monkeypatch.setattr(spans, "_TRACER", object())
    run = types.SimpleNamespace(window=(10.0, 11.0), counters={})
    assert read(run, mode="self", unit="ms") is None
    assert run.counters == {}


def test_every_span_metric_names_a_mode_the_reader_has():
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    seen = 0
    for name in sorted(os.listdir(os.path.join(here, "metrics"))):
        with open(os.path.join(here, "metrics", name)) as f:
            spec = json.load(f)
        if spec["reader"] != "span_ms":
            continue
        seen += 1
        args = spec["args"]
        assert args["mode"] in ("sum", "union", "self", "count",
                                "mean_nbytes"), name
        assert args.get("pick") or args.get("stages") or \
            args["mode"] == "self", name
        if args["mode"] in ("sum", "union", "self"):
            assert args["unit"] in span_ms.UNIT_US, name
        # the reader must get through a run with no such span
        assert span_ms.read(make_run(), args) is None or name
    assert seen >= 20
