"""The simulator front end drives a whole run at a tiny size on the CPU,
through the harness's internal entry, and the check refuses a broken
program and the lower-precision control."""
import json
import shutil
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

import _paths
from harness import resolve, run_cell

SPEC = json.loads((_paths.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


def shrink(run):
    run.config = dict(run.config, n_nodes=40, trace_slots=24, n_slots=9,
                      check_slots=6, arrivals_per_slot=256, retry_capacity=64)
    run.mix = dict(run.mix, n_tasks=1400)
    return run


def tiny(cell):
    return shrink(resolve(SPEC, cell))


def run_tiny(cell, seed=5, trace=0, seconds=0.3, run=None, entry=run_cell):
    args = SimpleNamespace(workload=cell, seed=seed, seconds=seconds,
                           trace=trace)
    return entry(run or tiny(cell), args, jax.devices()[:1],
                 time.perf_counter())


@pytest.fixture
def fresh_programs():
    """Traced programs are cached; a patched admission core needs them
    traced again, before and after."""
    from repro.api import admission

    def clear():
        jax.clear_caches()
        admission._shared_queue_admitter.cache_clear()

    clear()
    yield
    clear()


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_a_tiny_run_is_correct(cell, trace):
    line = run_tiny(cell, trace=trace)
    assert line["correct"], line["checks"]
    assert all(c["value"] == 0 for c in line["checks"].values())
    assert line["attempted"] > 0 and line["failed"] == 0
    if trace:
        assert "setup_s" not in line["metrics"]
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        # the recording starts in the second study, timed by the first
        assert line["info"]["studies"] >= 2
        assert line["device"]["window_s"] > 0
    else:
        assert line["metrics"]["setup_s"]["value"] > 0
        assert line["metrics"]["sim_slots_per_s"]["value"] > 0
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("trace", [0, 1])
def test_a_cell_added_as_data_reports_its_front_ends_metrics(tmp_path,
                                                             trace):
    """A cell that no metric's ``workloads`` names gets the end-to-end
    metrics its front end measures, with no edit of the harness."""
    from harness import load_module

    bench = _paths.BENCH
    shutil.copytree(bench, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("_out", "__pycache__"))
    mix = json.loads((bench / "traffic" / "overload16.json").read_text())
    (tmp_path / "chipbench" / "traffic" / "overload13.json").write_text(
        json.dumps(dict(mix, offered_load=1.3)))
    spec = json.loads(json.dumps(SPEC))
    for m in spec["end_to_end"] + spec["per_layer"]:
        m["workloads"] = list(CELLS)
    cell = "sim.gct4000.overload13"
    spec["workloads"].append({"name": cell, "config": "gct2011-4000",
                              "traffic": "overload13", "chips": 1,
                              "why": "a test cell"})
    copy = load_module(tmp_path / "chipbench" / "harness.py", "copied")
    run = shrink(copy.resolve(spec, cell))
    line = run_tiny(cell, trace=trace, run=run, entry=copy.run_cell)
    assert line["correct"], line["checks"]
    if not trace:
        assert set(line["metrics"]) == {"setup_s", "sim_slots_per_s"}


def _broken(kind, orig):
    def admit_queue(policy, node, requests, srcs, priorities, valid, *a,
                    **kw):
        if kind == "half":
            valid = valid & (jnp.arange(valid.shape[0]) % 2 == 0)
        new_node, placed = orig(policy, node, requests, srcs, priorities,
                                valid, *a, **kw)
        if kind == "unchanged":
            return node, jnp.full_like(placed, -1)
        if kind == "altered":
            n = node.n_tasks.shape[0]
            placed = jnp.where(placed >= 0, (placed + 1) % n, placed)
        return new_node, placed
    return admit_queue


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("kind", ["altered", "unchanged", "half"])
def test_a_broken_admission_core_is_not_correct(cell, kind, monkeypatch,
                                                fresh_programs):
    from repro.api import admission

    monkeypatch.setattr(admission, "admit_queue",
                        _broken(kind, admission.admit_queue))
    line = run_tiny(cell, seed=8)
    assert not line["correct"], line["checks"]


def test_lower_precision_controls_fail():
    """The reference in bfloat16, put in the program's place, reads over
    the configuration's limits on every seed tried."""
    from harness import load_module
    from reference import sim_ref

    sim = load_module(_paths.BENCH / "frontends" / "sim.py", "sim_fe")
    for cell in CELLS:
        run = tiny(cell)
        conf = dict(run.config, n_slots=run.config["check_slots"])
        for seed in (1, 2, 3):
            tasks = sim.make_tasks(conf, run.mix, seed)
            ref = sim_ref.run_reference(conf, tasks, seed)
            low = sim_ref.run_reference(conf, tasks, seed,
                                        dtype=jnp.bfloat16)
            got = sim.compare(low, ref, conf["n_slots"], tasks["arrival"])
            assert any(got[k] > lim for k, lim in
                       run.config["check_limits"].items()), (cell, seed, got)
