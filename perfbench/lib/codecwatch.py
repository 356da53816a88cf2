"""Under --trace 1 only: note every batch the stripe codec encodes (when,
how many stripes, of what shape), so that the roofline reader can set the
device time of the encode program against the work its calls needed. The
wrapper passes arguments and results through untouched."""

from __future__ import annotations

import time


def watch(ctx) -> None:
    from tpu3fs.ops.stripe import StripeCodec

    inner = StripeCodec.encode_batch
    if getattr(inner, "_pb_watched", False):
        return

    def encode_batch(self, data):
        t0 = time.perf_counter()
        with ctx.jax.profiler.TraceAnnotation("pb:codec.encode_batch"):
            out = inner(self, data)
        ctx.codec_calls.append((t0, time.perf_counter(), int(data.shape[0]),
                                self.k, self.m, self.shard_size,
                                self._use_host()))
        return out

    encode_batch._pb_watched = True
    StripeCodec.encode_batch = encode_batch
