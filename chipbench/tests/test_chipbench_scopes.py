"""Named scopes: the compiled program carries them, and the reduction puts
device time down to them per slot."""
import json
import re

import jax
import jax.numpy as jnp
import pytest

import _paths
import scopes
from tracing import Reduced, TRACE_END, TRACE_START, opcode
from test_chipbench_frontends import tiny

SLOT_PASSES = ("aggregates", "demand", "allocate", "penalty", "estimate",
               "admit", "retry", "metrics")
OPTIONAL = ("faults", "migrate", "reclaim")
LEAF_FREE = {"parameter", "constant", "get-tuple-element", "tuple",
             "bitcast"}


def _program(extra):
    """(lowered text with locations, compiled text) of ``simulate_core``
    at the tiny size of the front-end tests, the cell's configuration
    with ``extra`` SimConfig fields."""
    from harness import load_module
    from repro.api import Experiment
    from repro.core import FlexParams, TaskSet, simulator

    run = tiny("sim.gct4000.overload")
    fe = load_module(run.frontend, "scopes_frontend")
    tasks = fe.make_tasks(run.config, run.mix, 5)
    ts = TaskSet(**{k: jnp.asarray(v) for k, v in tasks.items()})
    estimator = extra.pop("estimator", run.config["estimator"])
    exp = Experiment(ts, fe.sim_config(run.config)._replace(**extra),
                     policy=run.config["policy"],
                     params=FlexParams.default(qos_target=0.99),
                     estimator=estimator)
    low = simulator.simulate_core.lower(
        exp.trace, exp.arrival_table, exp.cluster, exp.policy, exp.params,
        jax.random.PRNGKey(0), exp.estimator, exp.controller)
    return low.as_text(debug_info=True), low.compile().as_text()


def _every_pass():
    from repro.faults import FaultConfig
    from repro.guard import GuardConfig
    from repro.migration import MigrationConfig

    return dict(faults=FaultConfig(degrade=True), migration=MigrationConfig(),
                reclamation=True, guard=GuardConfig(), estimator="ewma")


def _executed(text):
    """(instruction, opcode) of the ops a device runs: the fusions and
    leaf instructions outside fused, reducer and comparator computations
    and outside the entry computation (which runs once per study, not per
    slot)."""
    inner = set(re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", text))
    for group in re.findall(r"called_computations=\{([^}]*)\}", text):
        inner |= {c.strip().lstrip("%") for c in group.split(",")}
    out, comp = [], None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$", line)
        if head:
            comp = None if head.group(1) else head.group(2)
            continue
        m = re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s", line)
        op = opcode(line)
        if m and comp is not None and comp not in inner \
                and op not in LEAF_FREE:
            out.append((m.group(1), op))
    return out


@pytest.mark.parametrize("case", ["cell", "every_pass"])
def test_every_enabled_pass_scope_is_in_the_compiled_program(case):
    extra = _every_pass() if case == "every_pass" else {}
    enabled = {f"flex.slot.{p}" for p in SLOT_PASSES} | {scopes.ADMISSION}
    if case == "every_pass":
        enabled |= {f"flex.slot.{p}" for p in OPTIONAL}
    lowered, compiled = _program(extra)
    assert set(re.findall(r"flex\.[a-z.]+", lowered)) == enabled
    names = scopes.op_names(compiled)
    found = {scopes.scope(o) for o in names.values()} - {scopes.UNSCOPED}
    # the cell's `current` estimator hands the measured usage on as the
    # estimate: its pass does no device work, and compiles to nothing
    assert found == (enabled if case == "every_pass"
                     else enabled - {"flex.slot.estimate"})
    executed = _executed(compiled)
    assert {op for _, op in executed} >= {"fusion", "while"}
    # every op run in the slot scan is mapped, with an op_name
    assert all(names.get(i) for i, _ in executed)
    scans = [names[i] for i, op in executed if op == "while"
             and names[i].endswith(scopes.ADMISSION + "/while")]
    if case == "cell":
        assert scans == ["jit(simulate_core)/while/body/closed_call/"
                         "flex.slot.admit/flex.admission/while"]
    for p in ("admit", "migrate", "reclaim"):
        if f"flex.slot.{p}" in enabled:
            assert any(f"flex.slot.{p}/{scopes.ADMISSION}/" in n
                       for n in scans)


def test_the_slot_period_comes_from_the_admission_scan_of_the_program():
    _, compiled = _program({})
    names = scopes.op_names(compiled)
    scan = next(i for i, op in _executed(compiled) if op == "while"
                and names[i].endswith(scopes.ADMISSION + "/while"))
    slot = next(i for i, o in names.items() if i.startswith("while")
                and o == "jit(simulate_core)/while")
    events = [(f"%{slot} = (s32[]) while(s32[] %t)", 0, 10_000)]
    events += [(f"%{scan} = (s32[]) while(s32[] %t)", s, s + 60)
               for s in (100, 340, 580, 830, 1070)]
    assert scopes.slot_period_ns(events, names) == 240.0


def test_scope_is_the_outermost_slot_pass_or_the_admission_core():
    assert scopes.scope("jit(f)/while/body/flex.slot.admit/flex.admission/"
                        "while/body/add") == scopes.ADMISSION
    assert scopes.scope("jit(f)/while/body/flex.slot.reclaim/flex.admission"
                        ) == scopes.ADMISSION
    assert scopes.scope("jit(f)/while/body/flex.slot.demand/jit(clip)/max"
                        ) == "flex.slot.demand"
    assert scopes.scope("jit(f)/while/body/dynamic_update_slice"
                        ) == scopes.UNSCOPED
    assert scopes.scope("") == scopes.UNSCOPED


HLO = """\
HloModule jit_f, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/while/body/flex.slot.demand/add"}
}

%fused_computation.2 (param_0.1: f32[8]) -> f32[] {
  %param_0.1 = f32[8]{0} parameter(0)
  %c.1 = f32[] constant(0), metadata={op_name="jit(f)/while/body"}
  %neg.1 = f32[8]{0} negate(%param_0.1), metadata={op_name="jit(f)/while/body/flex.slot.admit/flex.admission/neg"}
  ROOT %reduce.1 = f32[] reduce(%neg.1, %c.1), dimensions={0}, to_apply=%sum
}

%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%p), index=1
  %fusion.1 = f32[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1
  %my-fusion.2 = f32[] fusion(%gte.1), kind=kLoop, calls=%fused_computation.2
  %copy.3 = f32[8]{0} copy(%fusion.1)
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte.0, %copy.3)
}

ENTRY %main.9 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %copy.9 = f32[8]{0} copy(%a)
  ROOT %while.1 = (s32[], f32[8]{0}) while(%t), condition=%cond.1, body=%body.1, metadata={op_name="jit(f)/while"}
}
"""


def test_op_names_cover_instructions_without_metadata():
    names = scopes.op_names(HLO)
    assert names["add.1"].endswith("flex.slot.demand/add")
    # a fusion takes its root's op_name ...
    assert scopes.scope(names["fusion.1"]) == "flex.slot.demand"
    # ... or the scope most of its scoped instructions are under
    assert scopes.scope(names["my-fusion.2"]) == scopes.ADMISSION
    # a copy the compiler put in takes the while's that runs its body
    assert names["copy.3"] == "jit(f)/while"
    assert names["copy.9"] == ""
    assert names["while.1"] == "jit(f)/while"
    assert scopes.instruction(
        "%my-fusion.2 = f32[] fusion(f32[8]{0} %gte.1), kind=kLoop, "
        "calls=%fused_computation.2") == "my-fusion.2"


NAMES = {"d.1": "jit(f)/while/body/flex.slot.demand/mul",
         "a.2": "jit(f)/while/body/flex.slot.admit/flex.admission/add",
         "m.3": "jit(f)/while/body/dynamic_update_slice",
         "w.4": "jit(f)/while/body/flex.slot.admit/flex.admission/while",
         "w.5": "jit(f)/while/body/flex.slot.admit/flex.admission/while/"
                "body/while"}


def _op(name, s, e):
    return (f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %x)", s, e)


def _while(name, s, e):
    return (f"%{name} = (s32[]) while((s32[]) %t), condition=%c, body=%b",
            s, e)


def _trace():
    ops = [_op("d.1", 100, 300), _op("a.2", 250, 400),   # overlap 50
           _op("a.2", 450, 500),                          # gap 50 before
           _op("m.3", 500, 520), _op("z.9", 600, 650),   # z.9: no map
           _op("d.1", 700, 900), _op("a.2", 1000, 1150)]
    spans = {TRACE_START: [(100, 101)], TRACE_END: [(1150, 1200)]}
    return Reduced([{"ops": ops, "modules": []}], spans)


def test_busy_time_and_the_gap_before_an_op_go_to_its_scope():
    t = _trace()
    got = scopes.attribute(t.devices[0]["ops"], t.lo, t.hi, NAMES)
    assert got == {"flex.slot.demand": [400.0, 50.0],
                   scopes.ADMISSION: [300.0, 150.0],
                   scopes.UNSCOPED: [20.0, 0.0],
                   scopes.UNMAPPED: [50.0, 80.0],
                   scopes.TAIL: [0.0, 50.0]}
    assert sum(b for b, _ in got.values()) == pytest.approx(
        t.busy_s() * 1e9)
    assert sum(b + i for b, i in got.values()) == t.hi - t.lo


def test_per_slot_counts_slots_from_the_outermost_admission_while():
    t = _trace()
    whiles = ([_while("w.4", s, s + 150) for s in (250, 520, 800, 1050)]
              + [_while("w.5", s, s + 10) for s in (260, 270, 280)])
    table = scopes.per_slot(t, [whiles], NAMES)
    assert table["slot_period_ms"] == pytest.approx(270 / 1e6)
    assert table["slots"] == pytest.approx(1100 / 270)
    per = table["slots"] * 1e6
    assert table["ms_per_slot"][scopes.ADMISSION] == pytest.approx(450 / per)
    assert table["idle_ms_per_slot"]["flex.slot.demand"] == pytest.approx(
        50 / per)
    assert sum(table["share"].values()) == pytest.approx(1.0)
    assert scopes.layer_ms(table, "admission") == pytest.approx(450 / per)
    assert scopes.layer_ms(table, "slot_pipeline") == pytest.approx(
        450 / per)


def test_no_reading_without_a_program_text_or_an_admission_scan():
    t = _trace()
    whiles = [_while("w.4", s, s + 150) for s in (250, 520, 800)]
    assert scopes.per_slot(t, [whiles], {}) is None
    assert scopes.per_slot(t, [[]], NAMES) is None
    assert scopes.per_slot(t, [[_while("w.4", 250, 400)]], NAMES) is None
    assert scopes.per_slot(Reduced([], {}), [], NAMES) is None
    assert scopes.layer_ms(None, "admission") is None
    with pytest.raises(ValueError):
        scopes.layer_ms(scopes.per_slot(t, [whiles], NAMES), "kernels")


def test_the_scope_profile_tool_runs_a_tiny_cell(tmp_path):
    """On the CPU the trace holds no TPU plane, so there is no table; the
    rest of the row (the untraced rate, the program's text) is there."""
    from harness import load_module

    tool = load_module(_paths.BENCH / "tools" / "scope_profile.py",
                       "scope_profile")
    row = tool.profile(tiny("sim.gct4000.trace"), 5, 0.3,
                       tmp_path / "trace")
    assert row["workload"] == "sim.gct4000.trace"
    assert row["untraced"]["sim_slots_per_s"] > 0
    assert row["traced"]["studies"] >= 2
    assert row["instructions"] > row["scoped_instructions"] > 100
    assert row["scopes"] is None and "top_ops" not in row
    json.dumps(row)
