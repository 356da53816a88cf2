"""End-to-end readers on hand-made requests."""

import types

import pytest

from perfbench.lib.proxies import SpanLog
from perfbench.readers import (percentile, phase_median, proxy_median, rate,
                               window_per_request)


def req(i, t0, t1, ok=True, **kw):
    return {"id": i, "t0": t0, "t1": t1, "ok": ok, "load_bytes": 0,
            "store_bytes": 0, "phases": {}, **kw}


def test_rate_is_all_bytes_over_all_time():
    run = types.SimpleNamespace(requests=[
        req(0, 10.0, 12.0, load_bytes=4 << 20),
        req(1, 11.0, 15.0, load_bytes=2 << 20),
        req(2, 12.0, 13.0, ok=False, load_bytes=99 << 20)])
    assert rate.read(run, {"field": "load_bytes", "scale": 1 << 20}) == \
        pytest.approx(6 / 5.0)
    assert window_per_request.read(run, {}) == pytest.approx(5.0 / 2)
    assert rate.read(types.SimpleNamespace(requests=[]),
                     {"field": "load_bytes"}) is None


def test_percentile_counts_every_request_and_failures_as_missing():
    reqs = [req(i, 0.0, (i + 1) / 1000.0) for i in range(100)]
    run = types.SimpleNamespace(requests=reqs)
    assert percentile.read(run, {"q": 95}) == pytest.approx(95.0)
    assert percentile.read(run, {"q": 50}) == pytest.approx(50.0)
    reqs[0]["ok"] = False               # reads as the window's length
    reqs[1]["ok"] = False
    reqs += [req(100 + i, 0.0, 0.001) for i in range(20)]
    assert percentile.read(run, {"q": 99}) == pytest.approx(100.0)


def test_proxy_and_phase_medians():
    log = SpanLog()
    for rid, dts in ((0, (0.010, 0.020)), (1, (0.050,)), (2, (0.002,))):
        log.set_request(rid)
        for dt in dts:
            log.add("meta", "stat", 5.0, 5.0 + dt)
    log.set_request(1)
    log.add("fio", "read", 5.0, 5.5)
    log.add("meta", "stat", 1.0, 3.0)            # before the window
    run = types.SimpleNamespace(
        spans=log, window=(4.0, 9.0),
        requests=[req(0, 4, 5, phases={"land": 0.004}),
                  req(1, 4, 5, phases={"land": 0.002}),
                  req(2, 4, 5, phases={"land": 0.009})])
    assert proxy_median.read(run, {"layer": "meta"}) == pytest.approx(30.0)
    assert proxy_median.read(run, {"layer": "fio", "ops": ["read"]}) == \
        pytest.approx(500.0)
    assert proxy_median.read(run, {"layer": "fio", "ops": ["write"]}) is None
    assert proxy_median.read(run, {"layer": "meta", "per": "request_count"}) \
        == pytest.approx(82.0 / 3)
    assert phase_median.read(run, {"phase": "land", "scale": 1000}) == \
        pytest.approx(4.0)
    assert phase_median.read(run, {"phase": "save"}) is None
