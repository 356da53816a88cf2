"""1 - (union of the device's operation intervals) / (traced window), in
percent, from the profiler's trace."""


def read(run, args):
    t = run.trace_data
    if not t or t["window_s"] <= 0 or t["devices"] == 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
