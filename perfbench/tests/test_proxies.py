"""A timing proxy passes every attribute through and changes no result."""

import threading

import pytest

from perfbench.lib.proxies import SpanLog, TimingProxy


class Thing:
    kind = "thing"

    def __init__(self):
        self.calls = 0
        self.value = 41

    def bump(self, by=1, *, twice=False):
        self.calls += 1
        self.value += by * (2 if twice else 1)
        return self.value

    def boom(self):
        raise KeyError("boom")

    @property
    def storage(self):
        return "the client behind"


def test_results_attributes_and_errors_pass_through():
    log, real = SpanLog(), Thing()
    proxy = TimingProxy(real, "fio", log)
    log.set_request(7)
    assert proxy.bump(2, twice=True) == real.value == 45
    assert proxy.kind == "thing" and proxy.storage == "the client behind"
    assert proxy.value == 45 and proxy.calls == 1
    proxy.value = 10                      # writes land on the real object
    assert real.value == 10
    with pytest.raises(KeyError):
        proxy.boom()
    with pytest.raises(AttributeError):
        proxy.nothing_here
    assert getattr(proxy, "nothing_here", None) is None
    assert set(dir(real)) <= set(dir(proxy))
    ops = [(layer, op, rid) for layer, op, rid, _, _ in log.spans]
    assert ops == [("fio", "bump", 7), ("fio", "boom", 7)]
    assert all(t1 >= t0 for *_, t0, t1 in log.spans)


def test_request_ids_are_per_thread():
    log, real = SpanLog(), Thing()
    proxy = TimingProxy(real, "meta", log)

    def work(rid):
        log.set_request(rid)
        proxy.bump()

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert sorted(rid for _, _, rid, _, _ in log.spans) == list(range(8))


def test_the_programs_clients_work_through_a_proxy():
    """KVCacheClient over proxied in-process meta and fio: same bytes."""
    from tpu3fs.fabric import Fabric
    from tpu3fs.kvcache import KVCacheClient

    fab = Fabric()
    try:
        log = SpanLog()
        plain = KVCacheClient(fab.meta, fab.file_client(), root="/a")
        proxied = KVCacheClient(TimingProxy(fab.meta, "meta", log),
                                TimingProxy(fab.file_client(), "fio", log),
                                root="/b")
        for c in (plain, proxied):
            c.batch_put([("k1", b"x" * 5000), ("k2", b"y" * 70000)])
        assert plain.batch_get(["k1", "k2", "k3"]) == \
            proxied.batch_get(["k1", "k2", "k3"])
        assert {layer for layer, *_ in log.spans} == {"meta", "fio"}
    finally:
        fab.close()
