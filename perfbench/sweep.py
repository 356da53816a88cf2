#!/usr/bin/env python3
"""perfbench/sweep.py: one cell over many seeds, one process a seed, back to
back in this checkout, as the driver's check runs them.

    python3 perfbench/sweep.py --workload <name> [--seeds 12] [--seconds 8]
        [--first-seed N] [--sets 1] [--trace-last K] [--fault NAME]
        [--rehearse-cpu] [--out chiprun_out/<file>.json]

Stops at the first run whose `correct` is false (or that prints no result),
says which comparison failed and shows the end of that run's errors, which
carries the cluster's log tails. With --fault the runs are expected to come
out false and the sweep says whether each did. With --sets 2 the same seeds
run twice and the spread of every metric (distance between the quartiles
over the median, as statistics.quantiles gives them) is printed per set.
This process never touches JAX: each run owns the chip in turn.
"""

import argparse
import json
import os
import re
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def one_run(args, seed: int, trace: int) -> dict:
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
           args.workload, "--seed", str(seed), "--seconds",
           str(args.seconds), "--trace", str(trace)]
    if args.fault:
        cmd += ["--fault", args.fault]
    if args.rehearse_cpu:
        cmd += ["--rehearse-cpu"]
    t0 = time.time()
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    out = {"seed": seed, "trace": trace, "rc": p.returncode,
           "wall_s": time.time() - t0, "stderr_tail": p.stderr[-6000:]}
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    if args.rehearse_cpu:
        # a rehearsal prints no result line; it says what it compared
        compared = {}
        for ln in p.stderr.splitlines():
            m = re.match(r"compared (\S+): (\S+) \(limit (\S+)\)", ln)
            if m:
                compared[m.group(1)] = {"value": float(m.group(2)),
                                        "limit": float(m.group(3))}
        out["result"] = {"correct": p.returncode == 0 and bool(compared),
                         "metrics": {}, "compared": compared}
        return out
    try:
        out["result"] = json.loads(lines[-1])
    except (IndexError, ValueError):
        out["result"] = None
    return out


def spread(values: list) -> float:
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else float("inf")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2147480000)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace-last", type=int, default=0,
                    help="run the last K seeds of the first set with "
                         "--trace 1")
    ap.add_argument("--fault", default="")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    runs, stopped = [], False
    for s in range(args.sets):
        for i, seed in enumerate(seeds):
            trace = int(s == 0 and i >= len(seeds) - args.trace_last)
            r = one_run(args, seed, trace)
            r["set"] = s
            runs.append(r)
            res = r["result"]
            ok = bool(res and res.get("correct"))
            nums = {k: round(v["value"], 4)
                    for k, v in (res or {}).get("metrics", {}).items()}
            print(f"set {s} seed {seed} trace {trace}: rc={r['rc']} "
                  f"correct={res and res.get('correct')} "
                  f"wall={r['wall_s']:.0f}s {nums}", flush=True)
            if args.fault:
                print(f"  fault {args.fault}: "
                      + ("caught" if not ok else "NOT CAUGHT") + " "
                      + json.dumps({k: v for k, v in (res or {}).get(
                          "compared", {}).items()
                          if v["value"] > v["limit"]}), flush=True)
                continue
            if not ok:
                failed = {k: v for k, v in (res or {}).get(
                    "compared", {}).items() if v["value"] > v["limit"]}
                print(f"STOP: seed {seed} is not correct. Failed "
                      f"comparisons: {json.dumps(failed) or 'none printed'}"
                      f"\n--- end of that run's errors ---\n"
                      f"{r['stderr_tail']}", flush=True)
                stopped = True
                break
        if stopped:
            break
    if not args.fault and not stopped and not args.rehearse_cpu:
        for s in range(args.sets):
            rows = [r for r in runs if r["set"] == s and not r["trace"]]
            names = sorted({k for r in rows for k in r["result"]["metrics"]})
            for name in names:
                vals = [r["result"]["metrics"][name]["value"] for r in rows
                        if name in r["result"]["metrics"]]
                if len(vals) >= 2:
                    print(f"set {s} {name}: median "
                          f"{statistics.median(vals):.4f} spread "
                          f"{100 * spread(vals):.2f}% min {min(vals):.4f} "
                          f"max {max(vals):.4f} n={len(vals)}", flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(runs, f)
    if args.fault:
        return 0 if all(not (r["result"] or {}).get("correct")
                        for r in runs) else 1
    return 1 if stopped else 0


if __name__ == "__main__":
    sys.exit(main())
