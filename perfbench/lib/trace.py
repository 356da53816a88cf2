"""From the profiler's trace to numbers: device busy time, the operations
that took most of it, the longest idle gaps named by what the host was
doing, and the device time of named programs.

Two steps, so that the arithmetic can be checked on a small recorded trace
(tests/data/): load_xplane() turns an .xplane.pb into a plain dict, and
reduce() works on that dict alone.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_MARK = "pb:"
# the CPU backend has no device plane: a rehearsal reads the PjRt CPU
# client's threads instead, so that this code runs there too
REHEARSAL_LINE = "tf_XLAPjRtCpuClient"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str, rehearse: bool = False) -> dict:
    """-> {"planes": [{"name", "device", "lines": [{"name", "events":
    [[name, start_ns, duration_ns], ...]}]}]}: device planes whole, host
    planes cut down to the benchmark's own marks."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            fake_dev = (rehearse and not is_dev
                        and line.name.startswith(REHEARSAL_LINE))
            events = []
            for ev in line.events:
                if ev.duration_ns <= 0:
                    continue
                if is_dev or fake_dev or ev.name.startswith(HOST_MARK):
                    events.append([ev.name, float(ev.start_ns),
                                   float(ev.duration_ns)])
            if not events:
                continue
            if fake_dev:
                planes.append({"name": f"/device:CPU-rehearsal:{line.name}",
                               "device": True,
                               "lines": [{"name": OPS_LINE,
                                          "events": events}]})
            else:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "device": is_dev,
                           "lines": lines})
    return {"planes": planes}


def short_name(name: str) -> str:
    """An operation's own name: the trace gives the whole HLO line
    ("%fusion.3 = u8[...] fusion(...)"), the breakdown wants "fusion.3"."""
    return name.split(" = ")[0].lstrip("%")[:80]


def union(intervals: list) -> list:
    """Merged, sorted [start, end) intervals."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _op_lines(plane: dict) -> list:
    named = [ln for ln in plane["lines"] if ln["name"] == OPS_LINE]
    return named or plane["lines"]


def _split_gap(a: float, b: float, marks: list) -> dict:
    """{host mark: ns} of the idle gap [a, b): each instant belongs to the
    innermost (shortest) mark that covers it, whatever thread made it; an
    instant no mark covers is "host.unmarked". So a gap as long as a whole
    checkpoint cycle is split among the calls made inside it."""
    over = [(max(a, s), min(b, s + d), d, name) for name, s, d in marks
            if s < b and s + d > a]
    edges = sorted([(lo, 1, d, name) for lo, _, d, name in over]
                   + [(hi, 0, d, name) for _, hi, d, name in over])
    out: dict = {}
    active: dict = {}
    at = a
    for t, opens, d, name in edges + [(b, 0, 0.0, "")]:
        if t > at:
            key = (min(active)[1][len(HOST_MARK):] if active
                   else "host.unmarked")
            out[key] = out.get(key, 0.0) + t - at
            at = t
        if opens:
            active[(d, name)] = active.get((d, name), 0) + 1
        elif name:
            active[(d, name)] -= 1
            if not active[(d, name)]:
                del active[(d, name)]
    return out


def reduce(trace: dict, window_s: float, top: int = 10) -> dict:
    """-> busy_s (union of device-operation intervals, mean over device
    planes), window_s, device_ops and idle_gaps (each at most `top`
    [name, seconds]), programs {module name: [seconds, calls]}."""
    devices = [p for p in trace["planes"] if p["device"]]
    marks = [ev for p in trace["planes"] if not p["device"]
             for ln in p["lines"] for ev in ln["events"]]
    every = [ev for p in trace["planes"] for ln in p["lines"]
             for ev in ln["events"]]
    # the traced window on the trace's own clock: first to last event,
    # the host's marks among them, so that idle time before the first and
    # after the last device operation is a gap like any other
    t_lo = min((s for _, s, _ in every), default=0.0)
    t_hi = max((s + d for _, s, d in every), default=0.0)
    busy = []
    op_time: dict = {}
    programs: dict = {}
    gaps: list = []
    for plane in devices:
        spans = []
        for ln in _op_lines(plane):
            for name, s, d in ln["events"]:
                spans.append((s, s + d))
                key = short_name(name)
                op_time[key] = op_time.get(key, 0.0) + d
        merged = union(spans)
        busy.append(sum(b - a for a, b in merged) / 1e9)
        edges = [[t_lo, t_lo]] + merged + [[t_hi, t_hi]]
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b > a:
                gaps.append((b - a, a, b))
        for ln in plane["lines"]:
            if ln["name"] != MODULES_LINE:
                continue
            for name, _, d in ln["events"]:
                key = re.sub(r"\(\d+\)$", "", name)
                sec, n = programs.get(key, (0.0, 0))
                programs[key] = (sec + d / 1e9, n + 1)
    n_dev = max(1, len(devices))
    gaps.sort(reverse=True)
    named: dict = {}
    for _length, a, b in gaps[:200]:
        for key, ns in _split_gap(a, b, marks).items():
            named[key] = named.get(key, 0.0) + ns / 1e9
    return {
        "window_s": float(window_s),
        "busy_s": sum(busy) / n_dev,
        "devices": len(devices),
        "device_ops": [[k, v / 1e9 / n_dev] for k, v in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / n_dev] for k, v in sorted(
            named.items(), key=lambda kv: -kv[1])[:top]],
        "programs": {k: [v[0], v[1]] for k, v in programs.items()},
    }
