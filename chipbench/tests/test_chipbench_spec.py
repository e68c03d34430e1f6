"""BENCHMARK.json: its shape, its names, and how cells find their files."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import _paths

ROOT, BENCH = _paths.ROOT, _paths.BENCH
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["chipbench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    from harness import resolve

    run = resolve(SPEC, cell)
    assert run.frontend.is_file()
    assert run.config["frontend"] == run.frontend.stem
    assert any(m["name"] == "setup_s" for m in run.e2e)
    assert len(run.e2e) >= 2 and run.per_layer
    for m in run.per_layer:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_metrics_named_for_a_cell():
    from harness import named_for

    metrics = [{"name": "a"}, {"name": "b", "workloads": ["x", "y"]},
               {"name": "c", "workloads": ["y"]}]
    assert named_for(metrics, "x") == {"b"}
    assert named_for(metrics, "y") == {"b", "c"}
    assert named_for(metrics, "z") == set()


def test_names_units_and_lines():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = ([c["name"] for c in SPEC["configs"]] + CELLS
             + [m["name"] for m in metrics]
             + [w["traffic"] for w in SPEC["workloads"]]
             + [k for c in SPEC["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    assert len(set(CELLS)) == len(CELLS)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    texts = ([w["why"] for w in SPEC["workloads"]]
             + [c["why"] for c in SPEC["configs"]]
             + [c["source"] for c in SPEC["configs"]]
             + [m["layer"] for m in SPEC["per_layer"]] + SPEC["command"])
    assert all(0 < len(t) <= 200 and "\n" not in t and "\t" not in t
               for t in texts)
    for m in SPEC["end_to_end"]:
        assert 0 < m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    ends = {m["name"] for m in SPEC["end_to_end"]}
    assert all(m["moves"] in ends for m in SPEC["per_layer"])


def test_each_config_file_states_its_deployment():
    for c in SPEC["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"]
        assert conf["source"] == c["source"]
        assert set(conf["reduced"]) == set(c["reduced"])
        for key in ("assumed", "guarantees", "check_limits", "frontend"):
            assert key in conf


def test_a_cell_added_as_files_is_found(tmp_path):
    """A new mix and a new cell are data: nothing in the harness changes."""
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("_out", "__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    mix = json.loads((BENCH / "traffic" / "overload16.json").read_text())
    mix["offered_load"] = 1.3
    (tmp_path / "chipbench" / "traffic" / "overload13.json").write_text(
        json.dumps(mix))
    spec["workloads"].append({"name": "sim.gct4000.overload13",
                              "config": "gct2011-4000",
                              "traffic": "overload13", "chips": 1,
                              "why": "a test cell"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    from harness import load_module

    copy = load_module(tmp_path / "chipbench" / "harness.py", "copied")
    run = copy.resolve(spec, "sim.gct4000.overload13")
    assert run.mix["offered_load"] == 1.3
    assert run.frontend == tmp_path / "chipbench" / "frontends" / "sim.py"
    assert run.e2e == SPEC["end_to_end"]
    assert run.per_layer == SPEC["per_layer"]


def test_run_without_a_tpu_exits_nonzero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", "7", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr
