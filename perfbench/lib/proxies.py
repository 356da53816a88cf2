"""Timing proxies: plain __getattr__ wrappers that the benchmark puts around
the `meta` and `fio` objects it hands to the program's client classes, under
--trace 1 only. Every attribute passes through; a callable is timed on the
host clock and marked in the profiler's trace (so idle gaps of the device
can be named); no argument and no result is touched.
"""

from __future__ import annotations

import threading
import time


class SpanLog:
    """Spans of one run, kept in memory: (layer, op, request id, t0, t1).
    The request a span belongs to is the one its thread is serving."""

    def __init__(self):
        self.spans: list = []
        self._local = threading.local()
        self._lock = threading.Lock()

    def set_request(self, rid) -> None:
        self._local.rid = rid

    def current_request(self):
        return getattr(self._local, "rid", None)

    def add(self, layer: str, op: str, t0: float, t1: float) -> None:
        with self._lock:
            self.spans.append((layer, op, self.current_request(), t0, t1))


class TimingProxy:
    def __init__(self, target, layer: str, log: SpanLog):
        object.__setattr__(self, "_pb_target", target)
        object.__setattr__(self, "_pb_layer", layer)
        object.__setattr__(self, "_pb_log", log)

    def __getattr__(self, name):
        value = getattr(object.__getattribute__(self, "_pb_target"), name)
        if not callable(value) or isinstance(value, type):
            return value
        layer = object.__getattribute__(self, "_pb_layer")
        log = object.__getattribute__(self, "_pb_log")

        def timed(*args, **kwargs):
            import jax

            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(f"pb:{layer}.{name}"):
                try:
                    return value(*args, **kwargs)
                finally:
                    log.add(layer, name, t0, time.perf_counter())

        timed.__name__ = getattr(value, "__name__", name)
        return timed

    def __setattr__(self, name, value):
        setattr(object.__getattribute__(self, "_pb_target"), name, value)

    def __dir__(self):
        return dir(object.__getattribute__(self, "_pb_target"))

