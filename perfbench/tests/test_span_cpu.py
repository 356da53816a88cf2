"""The CPU reader on a hand-written span list: each mode, threads counted
once, what reads -1, the window, and every way of finding nothing."""

import json
import os
import types

import pytest

from perfbench.readers import span_cpu

FIELDS = ("span_id", "parent_id", "op", "stage", "t_perf", "dur_us",
          "nbytes", "cpu_us", "tid")


def row(sid, parent, op, stage, t_ms, dur_ms, cpu_ms, tid):
    return (sid, parent, op, stage, t_ms / 1e3, dur_ms * 1e3, 0,
            cpu_ms * 1e3 if cpu_ms >= 0 else -1.0, tid)


def hop(sid, parent, t_ms, tid, cpu_ms, collect, wire):
    """A hop op with its `collect` (dur, cpu) and, where the reply carried
    stamps, its `wire` (dur) and `server_run`."""
    rows = [row(sid, parent, "rpc.client.ring", "", t_ms, 20, cpu_ms, tid),
            row(sid + "i", sid, "rpc.client", "issue", t_ms, 1, 0.5, tid),
            row(sid + "c", sid, "rpc.client", "collect", t_ms + 1,
                collect[0], collect[1], tid)]
    if wire is not None:
        rows += [row(sid + "w", sid, "rpc.client", "wire", t_ms + 2, wire,
                     -1, tid),
                 row(sid + "r", sid, "rpc.client", "server_run", t_ms + 1,
                     1, -1, tid)]
    return rows


# The window is 10.0 s .. 11.0 s.
# Load A, thread 1, 30 ms of CPU in 100: a nested stage of its own thread,
# an after-the-fact op (-1) that fans four hops out: two on pool thread 2
# (one after the other), one on thread 3, one run inline on thread 1.
# Load B was closed by another thread than opened it (-1): its two stages
# ran on thread 5. Load E has no measured row at all. Load F is small, all
# on thread 8. Load C began before the window; one row beneath it, on
# thread 6, began inside.
ROWS = [
    row("A", "", "kv.get", "", 10_000, 100, 30, 1),
    row("A1", "A", "kv.get", "decode", 10_001, 6, 5, 1),
    row("A1x", "A", "kv.get", "decode", 10_008, 50, -1, 1),
    row("A2", "A", "client.batch_read", "", 10_010, 60, -1, 1),
    *hop("h1", "A2", 10_011, 2, 4, collect=(15, 2), wire=9),
    *hop("h2", "A2", 10_011, 3, 3, collect=(12, 1), wire=0.5),
    *hop("h3", "A2", 10_035, 2, 2, collect=(10, 1), wire=None),
    *hop("h4", "A2", 10_050, 1, 2.5, collect=(3, 0.5), wire=2),
    row("B", "", "kv.get", "", 10_600, 50, -1, 4),
    row("B1", "B", "kv.get", "read", 10_601, 30, 10, 5),
    row("B1a", "B1", "fio.read", "plan", 10_602, 5, 4, 5),
    *hop("h5", "B1", 10_603, 5, 1, collect=(8, 1), wire=6),
    row("B2", "B", "kv.get", "put", 10_640, 5, 3, 5),
    row("E", "", "kv.get", "", 10_800, 40, -1, 7),
    row("E1", "E", "kv.get", "decode", 10_801, 30, -1, 7),
    row("Eh", "E", "rpc.client.ring", "", 10_802, 9, -1, 7),
    row("Ehc", "Eh", "rpc.client", "collect", 10_802, 8, -1, 7),
    row("Ehw", "Eh", "rpc.client", "wire", 10_803, 5, -1, 7),
    row("F", "", "kv.get", "", 10_900, 9, 2, 8),
    row("F1", "F", "kv.get", "decode", 10_901, 3, 1, 8),
    *hop("h6", "F", 10_904, 8, 0.5, collect=(4, 1), wire=1.75),
    row("C", "", "kv.get", "", 9_990, 500, 100, 1),
    row("C1", "C", "fio.read", "", 10_100, 20, 7, 6),
]


def make_run(rows=ROWS, fields=FIELDS, window=(10.0, 11.0)):
    run = types.SimpleNamespace(window=window, counters={})
    run.span_cpu_index = span_cpu.index_from(rows, fields)
    return run


def read(run=None, root="kv.get", **args):
    return span_cpu.read(run or make_run(), {"root": root, **args})


def test_cpu_counts_a_pool_once_a_thread_and_a_nested_span_never_twice():
    # A: its own 30, + 4 and 2 (thread 2's two tasks), + 3 (thread 3); the
    # nested stage and the inline hop ran inside the root's reading: 39.
    # B: the root reads -1, its two stages of thread 5 add 10 + 3; what is
    # nested in the first adds nothing: 13. E finds nothing. F: 2. The
    # MEAN of the three is 18 (their median would be 13).
    assert read(mode="cpu", unit="ms") == pytest.approx(18.0)
    assert read(mode="cpu", unit="us") == pytest.approx(18_000.0)
    assert read(mode="cpu", unit="s") == pytest.approx(0.018)
    # a root deeper in a tree is read the same way
    assert read(root="client.batch_read", mode="cpu", unit="ms") == \
        pytest.approx(4 + 3 + 2 + 2.5)
    one = make_run(window=(10.0, 10.5))
    assert read(one, mode="cpu", unit="ms") == pytest.approx(39.0)


def test_resume_is_the_wall_less_the_cpu_less_what_the_servers_explain():
    # A: 100 - 30 of its own thread - the union of the servers' stamps
    # beneath it (h1 and h2 at the same millisecond, h4: 2; h3 has none)
    # = 68. B and E read -1 at the root and are left out. F: 9 - 2 - 1.
    assert read(mode="resume", unit="ms") == pytest.approx((68 + 6) / 2)
    # a hop without stamps explains nothing: its wait is all queue
    only_h3 = [r for r in ROWS if r[0].startswith(("A", "h3"))
               and not r[0].startswith("A1")]
    assert read(make_run(only_h3), mode="resume", unit="ms") == \
        pytest.approx(70.0)
    # never below 0: CPU and service that overlap can pass the wall
    busy = [row("G", "", "kv.get", "", 10_000, 10, 9.5, 1),
            row("Gh", "G", "rpc.client.ring", "", 10_001, 8, -1, 1),
            row("Ghr", "Gh", "rpc.client", "server_run", 10_002, 4, -1, 1)]
    assert read(make_run(busy), mode="resume", unit="ms") == 0.0


def test_offcpu_sums_only_the_picked_spans_that_are_measured():
    # A: decode 6 - 5 = 1 (its -1 twin of 50 ms is left out); B: put
    # 5 - 3 = 2; E: nothing measured; F: decode 3 - 1 = 2
    assert read(mode="offcpu", unit="ms",
                stages=["kv.get.decode", "kv.get.put"]) == \
        pytest.approx((1 + 2 + 2) / 3)
    assert read(mode="offcpu", unit="ms", stages=["kv.get.read"]) == \
        pytest.approx(20.0)
    # `pick` and `under` as in span_ms: the hops beneath client.batch_read
    assert read(mode="offcpu", unit="ms", pick=["rpc.client.*"],
                under="client.*") == pytest.approx(
        (20 - 4) + (20 - 3) + (20 - 2) + (20 - 2.5))
    assert read(mode="offcpu", unit="ms", stages=["no.such"]) is None


def test_cores_takes_every_row_of_the_window_over_its_length():
    # A 39, B 13, and C1's 7: C began before the window and is left out,
    # the row beneath it on another thread began inside
    assert read(mode="cores") == pytest.approx((39 + 13 + 2 + 7) / 1000.0)
    assert read(make_run(window=(10.0, 10.5)), mode="cores") == \
        pytest.approx((39 + 7) / 500.0)
    # with C inside too, its own 100 ms count and C1's still do
    assert read(make_run(window=(9.5, 11.0)), mode="cores") == \
        pytest.approx((39 + 13 + 2 + 100 + 7) / 1500.0)
    assert read(make_run(window=(20.0, 21.0)), mode="cores") is None
    assert read(make_run(window=(10.0, 10.0)), mode="cores") is None


MODES = [{"mode": "cpu", "unit": "ms"}, {"mode": "resume", "unit": "ms"},
         {"mode": "offcpu", "unit": "ms", "stages": ["kv.get.*"]},
         {"mode": "cores"}]


@pytest.mark.parametrize("args", MODES, ids=[m["mode"] for m in MODES])
def test_cpu_nothing_to_read_is_none_never_zero(args):
    # rows of a program without the column
    old = [r[:7] for r in ROWS]
    assert read(make_run(old, FIELDS[:7]), **args) is None
    # every row not measured
    unmeasured = [r[:7] + (-1.0, r[8]) for r in ROWS]
    assert read(make_run(unmeasured), **args) is None
    # no rows, no such root, no root in the window
    assert read(make_run([]), **args) is None
    if args["mode"] != "cores":
        assert read(root="no.such", **args) is None
        assert read(make_run(window=(20.0, 21.0)), **args) is None


@pytest.mark.parametrize("args", MODES, ids=[m["mode"] for m in MODES])
def test_cpu_reads_the_tracer_s_rows_and_dropped_rows_silence_it(
        monkeypatch, args):
    from tpu3fs.analytics import spans

    tracer = spans.Tracer()
    ctx = spans.TraceContext("t", "s", profiled=True)
    hop_ctx = ctx.child()
    spans.add_span(ctx, "kv.get", "decode", 1.0, 0.05, t_perf=10.51,
                   cpu_us=20_000.0)
    spans.add_span(hop_ctx, "rpc.client", "collect", 1.0, 0.04,
                   t_perf=10.52, cpu_us=1000.0)
    spans.add_span(hop_ctx, "rpc.client", "server_run", 1.0, 0.03,
                   t_perf=10.53)
    tracer.end_op(hop_ctx, "rpc.client.ring", 1.0, 0.05, t_perf=10.52,
                  cpu_us=2000.0)
    tracer.finish_op(ctx, "kv.get", 1.0, 0.2, t_perf=10.5, cpu_us=50_000.0)
    monkeypatch.setattr(spans, "_TRACER", tracer)
    said = []
    run = types.SimpleNamespace(window=(10.0, 11.0), counters={},
                                say=said.append)
    want = {"cpu": 50.0, "resume": 120.0, "offcpu": 30.0, "cores": 0.05}
    assert read(run, **args) == pytest.approx(want[args["mode"]])
    assert run.counters == {"spans_captured": 5, "spans_with_cpu": 4}
    assert len(said) == 1 and "5 rows" in said[0] and "4 with" in said[0]
    monkeypatch.setattr(tracer, "_captured_total", 9)    # four rows went
    run = types.SimpleNamespace(window=(10.0, 11.0), counters={})
    assert read(run, **args) is None
    assert run.counters == {"spans_captured": 5, "spans_dropped": 4}


def test_cpu_of_a_program_without_the_sink_or_the_column(monkeypatch):
    """The parent of the PR that brought the column: the reader returns
    nothing and does not raise."""
    from tpu3fs.analytics import spans

    tracer = spans.Tracer()
    ctx = spans.TraceContext("t", "s", profiled=True)
    tracer.finish_op(ctx, "kv.get", 1.0, 0.2, t_perf=10.5, cpu_us=9.0)
    monkeypatch.setattr(spans, "_TRACER", tracer)
    monkeypatch.setattr(spans, "CAPTURED_FIELDS", tuple(
        f for f in spans.CAPTURED_FIELDS if f != "cpu_us"))
    run = types.SimpleNamespace(window=(10.0, 11.0), counters={})
    for args in MODES:
        assert read(run, **args) is None
    assert run.counters == {"spans_captured": 1}
    monkeypatch.setattr(spans, "_TRACER", object())
    run = types.SimpleNamespace(window=(10.0, 11.0), counters={})
    assert read(run, mode="cores") is None and run.counters == {}


def test_an_unknown_cpu_mode_is_refused():
    with pytest.raises(ValueError):
        read(mode="sum", unit="ms")


def test_every_cpu_metric_names_a_mode_the_reader_has():
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    seen = {}
    for name in sorted(os.listdir(os.path.join(here, "metrics"))):
        with open(os.path.join(here, "metrics", name)) as f:
            spec = json.load(f)
        if spec["reader"] != "span_cpu":
            continue
        args = spec["args"]
        seen[args["mode"]] = seen.get(args["mode"], 0) + 1
        if args["mode"] == "cores":
            assert set(args) == {"mode"}, name
        else:
            assert args["unit"] in span_cpu.UNIT_US and args["root"], name
        if args["mode"] == "offcpu":
            assert args.get("pick") or args.get("stages"), name
        # the reader gets through a run with no such span, and one with
        assert span_cpu.read(make_run(), args) is None \
            or args["mode"] == "cores", name
    assert seen == {"cpu": 7, "resume": 2, "offcpu": 1, "cores": 3}
