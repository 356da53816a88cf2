"""BENCHMARK.json against the contract's own rules, and against the data
files it names; the harness names no cell."""

import json
import os
import re
import subprocess
import sys

from perfbench.lib.harness import load_traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_limits():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert 1 <= len(b["paths"]) <= 16 and 1 <= b["run_seconds"] <= 51
    assert all(LINE.match(w) for w in b["command"]) and len(b["command"]) <= 32
    cells = len(b["workloads"])
    # a full check with the full 24 cells fits into 43200 s
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= cells <= 24 and 1 <= len(b["configs"]) <= 24
    names = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and LINE.match(c["source"])
        assert LINE.match(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank")) or "hidden" in k
                       or "intermediate" in k for k in c["reduced"])
        assert c["file"].startswith("perfbench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert c["source"] == cfg["source"]
        names.add(c["name"])
    assert len(names) == len(b["configs"])
    assert len({c["file"] for c in b["configs"]}) == len(names)
    assert len({c["source"] for c in b["configs"]}) == len(names)
    seen = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert LINE.match(w["why"])
        seen.add((w["config"], w["traffic"]))
    assert len(seen) == cells
    assert {w["config"] for w in b["workloads"]} == names
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(1, cells // 2)


def test_metrics():
    b = bench()
    cells = [w["name"] for w in b["workloads"]]
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) == len(b["end_to_end"]) <= 16
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= set(cells)
    assert "workloads" not in e2e["setup_s"]
    layers = set()
    names = set(e2e)
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["name"] not in names
        names.add(m["name"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert LINE.match(m["layer"]) and m["moves"] in e2e
        layers.add(m["layer"])
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", moved)) <= set(moved)
    for cell in cells:   # setup_s, one other end-to-end, one per-layer
        assert any(cell in m.get("workloads", cells) for m in b["end_to_end"]
                   if m["name"] != "setup_s")
        assert any(cell in m.get("workloads", cells) for m in b["per_layer"])


def test_every_named_file_is_there():
    b = bench()
    bench_dir = os.path.join(ROOT, "perfbench")

    def data(*parts):
        with open(os.path.join(bench_dir, *parts)) as f:
            return json.load(f)

    for w in b["workloads"]:
        cell = data("workloads", w["name"] + ".json")
        assert set(cell) == {"who", "control", "faults"}
        for fault in [cell["control"]] + cell["faults"]:
            assert os.path.isfile(os.path.join(bench_dir, "faults",
                                               fault + ".py"))
        driver = load_traffic(w["traffic"])["driver"]
        assert os.path.isfile(os.path.join(bench_dir, "drivers",
                                           driver + ".py"))
    for m in b["end_to_end"] + b["per_layer"]:
        # BENCHMARK.json is the one place a metric's unit, bound, layer and
        # cells are said; its file says only how to read it
        spec = data("metrics", m["name"] + ".json")
        assert set(spec) == {"reader", "args"}
        assert os.path.isfile(os.path.join(bench_dir, "readers",
                                           spec["reader"] + ".py"))
    for dirpath, _dirs, files in os.walk(bench_dir):
        for name in files:
            if "__pycache__" in dirpath:
                continue
            assert re.match(r"^[A-Za-z0-9_.\-]+$", name), name


def test_a_mix_that_extends_another_states_only_what_differs():
    base = load_traffic("sessions")
    mix = load_traffic("hit_replay")
    with open(os.path.join(ROOT, "perfbench", "traffic",
                           "hit_replay.json")) as f:
        own = json.load(f)
    assert own["extends"] == "sessions" and "driver" not in own
    assert mix["driver"] == base["driver"] and mix["name"] == "hit_replay"
    for key, value in base["params"].items():
        assert mix["params"][key] == own["params"].get(key, value)
    assert mix["params"]["store_suffix"] is False
    assert mix["rehearsal"] == base["rehearsal"]


def test_no_harness_code_names_a_cell_a_config_or_a_metric():
    b = bench()
    words = ([w["name"] for w in b["workloads"]]
             + [c["name"] for c in b["configs"]]
             + [m["name"] for m in b["per_layer"]])
    bench_dir = os.path.join(ROOT, "perfbench")
    for sub in ("lib", "drivers", "readers", "faults", "run.py",
                "sweep.py"):
        path = os.path.join(bench_dir, sub)
        files = [path] if path.endswith(".py") else [
            os.path.join(path, f) for f in os.listdir(path)
            if f.endswith(".py")]
        for fp in files:
            with open(fp) as f:
                code = f.read()
            for word in words:
                assert word not in code, (fp, word)


def test_alone_in_a_directory_it_prints_no_result(tmp_path):
    import shutil

    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         bench()["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_without_a_chip_it_prints_no_result():
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         bench()["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr
