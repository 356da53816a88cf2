"""Under --trace 1 only: note every batch the stripe codec reconstructs
(when, how many stripes, how many shards lost, of what shape), so that the
roofline reader can set the device time of the decode program against the
work its calls needed. The wrapper passes arguments and results through
untouched. The decode's twin of lib/codecwatch.py, with a list of its own
(`ctx.decode_calls`)."""

from __future__ import annotations

import time


def watch(ctx) -> None:
    from tpu3fs.ops.stripe import StripeCodec

    if not hasattr(ctx, "decode_calls"):
        ctx.decode_calls = []
    inner = StripeCodec.reconstruct_batch
    if getattr(inner, "_pb_watched", False):
        return

    def reconstruct_batch(self, present_idx, lost_idx, present):
        t0 = time.perf_counter()
        with ctx.jax.profiler.TraceAnnotation("pb:codec.reconstruct_batch"):
            out = inner(self, present_idx, lost_idx, present)
        ctx.decode_calls.append((t0, time.perf_counter(),
                                 int(present.shape[0]), self.k,
                                 len(lost_idx), self.shard_size,
                                 self._use_host()))
        return out

    reconstruct_batch._pb_watched = True
    StripeCodec.reconstruct_batch = reconstruct_batch
