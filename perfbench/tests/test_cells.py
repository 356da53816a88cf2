"""Every cell driven end to end on the CPU (the harness's look for a chip
skipped by --rehearse-cpu, tiny sizes, the real multi-process cluster):

- twice back to back in one checkout, same seed and another seed: both
  correct, nothing left running, nothing compiled inside the window;
- with the timed path broken underneath by the cell's control and by each
  of its faults (faults/<name>.py): `correct` comes out false, by a number.

About 15 s a run; kept out of tests/, so not part of tier-1.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cells():
    """Every cell of BENCHMARK.json with its control and faults
    (workloads/<name>.json)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    out = []
    for name in names:
        with open(os.path.join(ROOT, "perfbench", "workloads",
                               name + ".json")) as f:
            out.append({"name": name, **json.load(f)})
    return out


def rehearse(workload: str, seed: int, fault: str = "", trace: int = 0):
    cmd = [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
           "--workload", workload, "--seed", str(seed), "--seconds", "2",
           "--trace", str(trace), "--rehearse-cpu"]
    if fault:
        cmd += ["--fault", fault]
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                       timeout=600)
    compared = {}
    for line in p.stderr.splitlines():
        if line.startswith("compared "):
            name, rest = line[len("compared "):].split(": ", 1)
            value, limit = rest.split(" (limit ")
            compared[name] = (float(value), float(limit.split(")")[0]))
    return p, compared


@pytest.mark.parametrize("cell", [c["name"] for c in cells()])
def test_two_runs_back_to_back_are_correct(cell):
    for seed, trace in ((2147484001, 0), (2147484001, 1), (17, 0)):
        p, compared = rehearse(cell, seed, trace=trace)
        assert p.returncode == 0, p.stderr[-3000:]
        assert p.stdout.strip() == "", "a rehearsal prints no result line"
        assert compared and all(v <= lim for v, lim in compared.values())
        assert "'still_running': 0" in p.stderr
        assert "REHEARSAL" in p.stderr
    from perfbench.lib.cluster import service_processes

    assert service_processes() == [], "a service process outlived its run"


@pytest.mark.parametrize("cell,fault", [
    (c["name"], f) for c in cells()
    for f in dict.fromkeys([c["control"]] + c["faults"])])
def test_a_broken_timed_path_comes_out_not_correct(cell, fault):
    p, compared = rehearse(cell, 23, fault=fault)
    assert "FAULT planted" in p.stderr
    assert p.returncode != 0 and p.stdout.strip() == ""
    failed = {k for k, (v, lim) in compared.items() if v > lim}
    assert failed, f"{fault} failed no comparison:\n{p.stderr[-3000:]}"
