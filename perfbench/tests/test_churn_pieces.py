"""The pieces the full-cache cell brought: the plain cache it is held to,
the byte-share reader, the counter reader, and the driver's reading of the
collector's log."""

import types

from perfbench.lib import reference_cache as refcache
from perfbench.readers import counter, span_ms, span_share

FIELDS = ("span_id", "parent_id", "op", "stage", "t_perf", "dur_us",
          "nbytes")


def test_the_plain_cache_removes_oldest_touched_first_down_to_the_budget():
    c = refcache.Cache()
    for i, key in enumerate("abcd"):
        c.put(key, bytes(10), now=float(i))
    assert c.get("a", now=10.0) == bytes(10)       # a touch: a is newest
    assert c.get("zz", now=11.0) is refcache.MISS
    assert c.capacity_pass(budget=25) == ["b", "c"]
    assert sorted(c.entries) == ["a", "d"] and c.resident() == 20
    assert c.capacity_pass(budget=25) == []
    c.touch("d", now=12.0)
    assert c.capacity_pass(budget=10) == ["a"]


def test_the_verdict_is_exact_or_a_named_absence():
    want = b"rows"
    assert refcache.verdict(b"rows", want, named_removed=False)
    assert refcache.verdict(refcache.MISS, want, named_removed=True)
    assert not refcache.verdict(refcache.MISS, want, named_removed=False)
    assert not refcache.verdict(bytes(4), want, named_removed=True)  # zeros
    assert not refcache.verdict(b"", want, named_removed=True)   # an error


def test_the_reference_cache_imports_nothing_of_the_program():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(refcache))
    names = [n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)]
    names += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
              for a in n.names]
    assert not any("tpu3fs" in n for n in names), names


def row(sid, parent, op, stage, t_ms, dur_ms, nbytes=0):
    return (sid, parent, op, stage, t_ms / 1e3, dur_ms * 1e3, nbytes)


def test_span_share_is_one_share_over_all_the_window_s_ops():
    rows = [
        row("A", "", "kv.get", "", 10_000, 40, nbytes=900),
        row("A1", "A", "kv.get", "ram", 10_001, 1, nbytes=300),
        row("A2", "A", "kv.get", "fill", 10_002, 30, nbytes=600),
        row("B", "", "kv.get", "", 10_500, 5, nbytes=100),
        row("B1", "B", "kv.get", "ram", 10_501, 1, nbytes=100),
        row("C", "", "kv.get", "", 9_000, 5, nbytes=100),   # before it
        row("C1", "C", "kv.get", "ram", 9_001, 1, nbytes=100),
    ]
    run = types.SimpleNamespace(window=(10.0, 11.0), counters={})
    run.span_index = span_ms.Index(rows, FIELDS)
    args = {"root": "kv.get", "part": ["kv.get.ram"],
            "whole": ["kv.get.ram", "kv.get.fill"]}
    assert span_share.read(run, args) == 100.0 * 400 / 1000
    assert span_share.read(run, {**args, "root": "kv.put"}) is None
    run.span_index = None    # a program with no sink: nothing, no raise
    assert span_share.read(run, args) is None


def test_counter_reads_what_the_driver_left_and_nothing_else():
    run = types.SimpleNamespace(counters={"ticks": 3.5})
    assert counter.read(run, {"key": "ticks"}) == 3.5
    assert counter.read(run, {"key": "absent"}) is None


def test_the_driver_reads_ticks_and_the_trail_from_the_log():
    from perfbench.drivers import kv_churn

    log = ("kvcache-gc: removed /kv/s1/ab/cd/abcd mtime=12.500 bytes=589864\n"
           "kvcache-gc: root=/kv/s1 ttl_removed=0 capacity_removed=1 "
           "tenants=0 entries=910 resident=536776240 scan_s=2.125 "
           "remove_s=0.031\n"
           "kvcache-gc: stopped inside a pass (1 removed in all)\n")
    (tick,) = kv_churn.Driver.ticks_of(log)
    assert tick["capacity_removed"] == 1 and tick["entries"] == 910
    assert tick["scan_s"] == 2.125 and tick["resident"] == 536776240
    named = [m.group(1) for m in map(kv_churn.REMOVED.match,
                                     log.splitlines()) if m]
    assert named == ["/kv/s1/ab/cd/abcd"]
    assert kv_churn.runs_of([0, 1, 2, 5, 7, 8]) == [[0, 1, 2], [5], [7, 8]]
