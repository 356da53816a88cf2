"""The decode program's share of its roofline: the least time the chip could
take for the stripes the window's calls reconstructed (lib/work_decode.py,
from (B, k, lost, S) alone), over the device time of that program's events
in the trace. Finds nothing (returns None) where no call or no such program
is in the window (an older program has none of that name); never 0."""

from ..lib import work, work_decode


def read(run, args):
    t = run.trace_data
    if not t:
        return None
    t_lo, t_hi = run.window
    calls = [c for c in getattr(run, "decode_calls", [])
             if t_lo <= c[0] <= t_hi and not c[6]]
    device_s = sum(sec for name, (sec, _n) in t["programs"].items()
                   if args["program"] in name)
    if not calls or device_s <= 0:
        return None
    peaks = work.peaks_of(run.device["kind"])
    least = 0.0
    bounds = set()
    for _t0, _t1, b, k, lost, s, _host in calls:
        sec, bound = work.least_seconds(
            work_decode.decode_work(b, k, lost, s), peaks)
        least += sec
        bounds.add(bound)
    run.counters["decode_roofline_bound"] = "+".join(sorted(bounds))
    run.counters["decode_calls"] = len(calls)
    run.counters["decode_device_s"] = device_s
    return 100.0 * least / device_s
