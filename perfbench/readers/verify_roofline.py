"""The CRC32C verify program's share of its roofline: the least time the
chip could take for the rows the window's calls checked (lib/work_crc.py,
from (B, n) alone), over the device time of that program's events in the
trace. Finds nothing (returns None) where the driver noted no call, or no
such program is in the window (a program without the verify); never 0."""

from ..lib import work, work_crc


def read(run, args):
    t = run.trace_data
    if not t:
        return None
    t_lo, t_hi = run.window
    calls = [c for c in getattr(run, "crc_calls", []) if t_lo <= c[0] <= t_hi]
    device_s = sum(sec for name, (sec, _n) in t["programs"].items()
                   if args["program"] in name)
    if not calls or device_s <= 0:
        return None
    peaks = work.peaks_of(run.device["kind"])
    least = 0.0
    bounds = set()
    for _t0, _t1, b, n in calls:
        sec, bound = work.least_seconds(work_crc.verify_work(b, n), peaks)
        least += sec
        bounds.add(bound)
    run.counters["verify_roofline_bound"] = "+".join(sorted(bounds))
    run.counters["verify_calls"] = len(calls)
    run.counters["verify_device_s"] = device_s
    return 100.0 * least / device_s
