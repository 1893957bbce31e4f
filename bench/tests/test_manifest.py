"""BENCHMARK.json against the files of the harness, and the harness's
refusal to run without a chip."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import run

ROOT = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


BENCH = manifest()
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    spec = run.load_cell(cell)
    assert spec["config"]["name"] == [
        w["config"] for w in BENCH["workloads"] if w["name"] == cell][0]
    assert {"k", "batch", "seq", "check_steps", "trace_steps"} <= set(
        spec["traffic"])
    assert set(spec["checks"]["limits"]) and all(
        v > 0 for v in spec["checks"]["limits"].values())
    assert "setup_s" in spec["end_to_end"] and len(spec["end_to_end"]) > 1
    assert spec["per_layer"]


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_metric_has_reader_and_target(metric):
    m = [x for x in BENCH["per_layer"] if x["name"] == metric][0]
    assert callable(run.metric_reader(metric))
    e2e = {x["name"]: x for x in BENCH["end_to_end"]}
    assert m["moves"] in e2e
    for cell in m.get("workloads", CELLS):
        assert cell in CELLS
        assert cell in e2e[m["moves"]].get("workloads", CELLS)


def test_names_units_and_chips():
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in BENCH["workloads"]]:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)
    layers = {}
    for m in BENCH["per_layer"]:
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
        layers.setdefault(m["layer"], []).append(m["name"])
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A cell added as data files and an entry is found with no edit to
    any file that was there."""
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p)
    bench = manifest()
    bench["workloads"].append({
        "name": "extra_cell", "config": BENCH["configs"][0]["name"],
        "traffic": "extra_mix", "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "bench" / "traffic" / "extra_mix.json").write_text(
        json.dumps({"k": 1, "batch": 1, "seq": 64, "schedule_steps": 10,
                    "check_steps": 2, "trace_steps": 1}))
    (tmp_path / "bench" / "workloads" / "extra_cell.json").write_text(
        json.dumps({"limits": {"loss_gap": 1.0}}))
    spec = run.load_cell("extra_cell", root=str(tmp_path))
    assert spec["traffic"]["k"] == 1 and spec["chips"] == 1
    assert spec["per_layer"] == []  # listed metrics name their cells


def _run_no_chip(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0],
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_exits_without_a_tpu():
    p = _run_no_chip(ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_exits_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p)
    p = _run_no_chip(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
