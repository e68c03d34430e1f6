"""Trace reduction: busy union, idle share, top ops, idle gaps by span;
and the recorder that starts the profiler partway into a window."""
import time

import pytest

import _paths
import tracing
from tracing import Reduced, TRACE_END, TRACE_START

FIXTURE = _paths.BENCH / "tests" / "data" / "tpu_small.xplane.pb"


def test_union_and_coverage():
    s, e = tracing.union([5, 0, 2, 20], [7, 3, 4, 30])
    assert s.tolist() == [0, 5, 20] and e.tolist() == [4, 7, 30]
    assert tracing.covered(s, e, 1, 25) == 3 + 2 + 5


def test_opcode_and_stem():
    text = ("%while.58 = (s32[], f32[8,2]{1,0:T(8,128)}) while((s32[], "
            "f32[8,2]) %tuple.3), condition=%cond, body=%body")
    assert tracing.opcode(text) == "while"
    assert tracing.stem(text) == "while"
    fused = ("%fusion.2 = f32[]{:T(128)} fusion(f32[512,512]{1,0:T(8,128)} "
             "%x.1), kind=kOutput, calls=%fused_computation.2")
    assert tracing.opcode(fused) == "fusion"
    assert tracing.stem(fused) == "fusion"


def _synthetic():
    ops = [("%a.1 = f32[] add(f32[] %x, f32[] %y)", 100, 200),
           ("%b.2 = f32[] fusion(f32[] %x)", 150, 300),
           ("%custom.3 = f32[] custom-call(f32[] %x)", 500, 600),
           ("%c.4 = f32[] add(f32[] %x, f32[] %y)", 900, 1000)]
    modules = [("jit_admit(1)", 90, 310), ("jit_other(2)", 490, 1010)]
    spans = {TRACE_START: [(0, 1)], TRACE_END: [(999, 1100)],
             "chipbench.step": [(0, 450), (450, 1100)],
             "chipbench.admit": [(80, 320)]}
    return Reduced([{"ops": ops, "modules": modules}], spans)


def test_synthetic_window_busy_idle_and_attribution():
    r = _synthetic()
    assert r.window_s() == pytest.approx(1100e-9)
    assert r.busy_s() == pytest.approx(400e-9)       # 100-300, 500-600, 900-1000
    assert r.idle_share() == pytest.approx(1 - 400 / 1100)
    gaps = dict(r.idle_by_span())
    # gaps: 0-100 (admit? mid 50 -> step), 300-500 (mid 400 -> step),
    # 600-900 (mid 750 -> step), 1000-1100 (step)
    assert gaps == {"chipbench.step": pytest.approx(700e-9)}
    assert r.top_ops(2) == [["b", pytest.approx(150e-9)],
                            ["a", pytest.approx(100e-9)]]


def test_recorded_tpu_trace_known_answers():
    """A trace recorded on one v5e: three runs of a jitted 512x512 sin and
    matmul inside ``chipbench.admit`` spans, 20 ms of host sleep apart."""
    r = tracing.read(str(FIXTURE))
    assert len(r.devices) == 1
    assert [m[0] for m in r.devices[0]["modules"]] == [
        "jit__lambda(5980559382019054752)"] * 3
    # no start/end markers: the window is the extent of the device's ops
    assert r.lo == 71148423.0 and r.hi == 114955600.0
    assert r.busy_s() == pytest.approx(32.018e-6)
    assert r.idle_share() == pytest.approx(1 - 32.018e-6 / 0.043807177)
    assert len(r.spans["chipbench.admit"]) == 3
    assert r.top_ops() == [["fusion", pytest.approx(32.018e-6)]]
    (name, idle), = r.idle_by_span()
    assert name == "chipbench.step"
    assert idle == pytest.approx(0.043807177 - 32.018e-6)


def test_recorder_starts_late_and_holds_its_span(tmp_path):
    """The profiler starts ``after`` seconds in, marks its window and the
    label span, and stops at ``close()`` or after ``seconds``."""
    rec = tracing.Tracer(tmp_path)
    assert not rec.done()
    t0 = time.perf_counter()
    rec.start(0.2, 30.0, "chipbench.study")
    time.sleep(0.5)
    rec.close()
    assert rec.done() and time.perf_counter() - t0 < 20
    r = tracing.reduce(tmp_path)
    (study,) = r.spans["chipbench.study"]
    assert r.lo <= study[0] and study[1] <= r.hi
    assert 0 < r.window_s() < 20


def test_recorder_closed_before_its_start_records_nothing(tmp_path):
    rec = tracing.Tracer(tmp_path)
    rec.start(30.0, 1.0, "chipbench.study")
    rec.close()
    assert rec.done()
    with pytest.raises(FileNotFoundError):
        tracing.reduce(tmp_path)
