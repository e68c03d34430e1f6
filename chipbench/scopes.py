"""Time per slot of the program's named scopes, from a device trace.

The program names its layers with ``jax.named_scope``: one scope per
pass of the simulator's slot pipeline (``flex.slot.*``,
``core/simulator.py``) and ``flex.admission`` around the admission core
(``api/admission.py``).  A scope is metadata on the compiled program's
instructions (``metadata={op_name="..."}``), not an op.  The device
trace names each op by its instruction's text without that metadata, so
the scope of a traced op is looked up by instruction name in the text of
the program that ran (``Compiled.as_text()``).

An op's scope is the outermost ``flex.slot.*`` component of its
``op_name``, or ``flex.admission`` wherever that appears (the admission
core runs inside the admit, migrate and reclaim passes); an op under no
``flex.*`` scope is ``unscoped`` (the slot scan's own bookkeeping, such
as the per-slot write of the stacked metrics), and an op whose
instruction is not in the text is ``unmapped``.

Time is put down to scopes so that it adds up to the traced window: an
op's part of the busy union goes to its scope, and an idle gap goes to
the scope of the op that ends it, which the device was waiting to start
(a gap after the last op goes to ``after last op``).  Slots are counted
on the device: the slot period is the median spacing of the starts of
the outermost ``while`` under ``flex.admission``, the admission scan,
which runs once per slot in the sequential admission mode.
"""
from __future__ import annotations

import glob
import re
from collections import Counter, defaultdict

import numpy as np

from tracing import (CONTAINER_OPS, DEVICE_PREFIX, OPS_LINE, opcode)

ADMISSION = "flex.admission"
SLOT_PREFIX = "flex.slot."
UNSCOPED, UNMAPPED, TAIL = "unscoped", "unmapped", "after last op"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEES = re.compile(
    r"(?:calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)|(?:branch|called)_computations=\{([^}]*)\}")
_EVENT = re.compile(r"^%?([^\s=]+)\s=")


def op_names(hlo_text: str) -> dict:
    """{instruction name: op_name} of every instruction in the text.

    An instruction the compiler made without metadata (a copy it
    inserted, some fusions) takes the op_name of the root of the
    computation it calls, or one under the scope that most of that
    computation's scoped instructions are under, else that of the
    instruction that calls the computation it lies in; "" where none has
    one.
    """
    own, comp_of, callees, roots = {}, {}, {}, {}
    caller, inside = {}, defaultdict(list)
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMP.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = op.group(1) if op else None
        comp_of[name] = comp
        if op:
            inside[comp].append(op.group(1))
        if line.lstrip().startswith("ROOT"):
            roots[comp] = name
        called = []
        for single, many in _CALLEES.findall(line):
            called += [single] if single else [
                c.strip().lstrip("%") for c in many.split(",")]
        callees[name] = called
        for c in called:
            caller.setdefault(c, name)

    memo = {}

    def resolve(name, seen=()):
        if name in memo:
            return memo[name]
        if own.get(name) is not None:
            return own[name]
        if name in seen:
            return ""
        seen = seen + (name,)
        out = ""
        for c in callees.get(name, ()):
            if own.get(roots.get(c)):
                out = own[roots[c]]
            elif inside[c]:
                scoped = [o for o in inside[c] if scope(o) != UNSCOPED]
                ops = scoped or inside[c]
                top = Counter(map(scope, ops)).most_common(1)[0][0]
                out = next(o for o in ops if scope(o) == top)
            if out:
                break
        if not out and comp_of.get(name) in caller:
            out = resolve(caller[comp_of[name]], seen)
        memo[name] = out
        return out

    return {name: resolve(name) for name in own}


def scope(op_name: str) -> str:
    """The scope an op's time goes to."""
    parts = op_name.split("/")
    if ADMISSION in parts:
        return ADMISSION
    for p in parts:
        if p.startswith(SLOT_PREFIX):
            return p
    return UNSCOPED


def instruction(event_name: str) -> str:
    """The instruction name of a trace event's HLO text."""
    m = _EVENT.match(event_name)
    return m.group(1) if m else event_name.split(" ", 1)[0].lstrip("%")


def attribute(ops, lo, hi, names: dict) -> dict:
    """{scope: [busy ns, idle ns]} over [lo, hi): each op's part of the
    busy union, and the idle gap before it.  ``ops`` are (event name,
    start, end) in ns."""
    tot = defaultdict(lambda: [0.0, 0.0])
    reach = lo
    for name, s, e in sorted(ops, key=lambda o: o[1]):
        s, e = max(s, lo), min(e, hi)
        if e <= reach:
            continue
        op = names.get(instruction(name))
        t = tot[UNMAPPED if op is None else scope(op)]
        t[0] += e - max(s, reach)
        t[1] += max(s - reach, 0.0)
        reach = e
    if hi > reach:
        tot[TAIL][1] += hi - reach
    return dict(tot)


def slot_period_ns(containers, names: dict):
    """Median spacing of the starts of the outermost ``while`` under
    ``flex.admission``; None with fewer than two such starts."""
    starts = defaultdict(list)
    for name, s, _ in containers:
        if opcode(name) != "while":
            continue
        parts = names.get(instruction(name), "").split("/")
        if ADMISSION in parts and "while" not in parts[
                parts.index(ADMISSION) + 1:-1]:
            starts[instruction(name)].append(s)
    if not starts:
        return None
    s = np.sort(max(starts.values(), key=len))
    return float(np.median(np.diff(s))) if len(s) >= 2 else None


def per_slot(trace, containers, names):
    """The traced window put down to scopes, per slot, or None where the
    program text or the slot period is missing.

    ``trace`` is a ``tracing.Reduced``; ``containers`` one list of
    container events (``read_containers``) per device, in its order.
    Returns {"slot_period_ms", "slots", "ms_per_slot": {scope: ms},
    "idle_ms_per_slot": {scope: the idle part of those ms}, "share":
    {scope: share of the window}}, averaged over the devices.
    """
    if not names or not trace.devices:
        return None
    period = [slot_period_ns(c, names) for c in containers]
    if not period or any(p is None for p in period):
        return None
    period = float(np.mean(period))
    window = trace.hi - trace.lo
    slots = window / period
    tot, idle = Counter(), Counter()
    for d in trace.devices:
        for s, (b, i) in attribute(d["ops"], trace.lo, trace.hi,
                                   names).items():
            tot[s] += b + i
            idle[s] += i
    per = len(trace.devices) * slots * 1e6
    return {"slot_period_ms": period / 1e6, "slots": slots,
            "ms_per_slot": {s: v / per for s, v in tot.items()},
            "idle_ms_per_slot": {s: v / per for s, v in idle.items()},
            "share": {s: v / len(trace.devices) / window
                      for s, v in tot.items()}}


def layer_ms(table, layer: str):
    """Device ms per slot of a layer: ``admission`` (``flex.admission``)
    or ``slot_pipeline`` (every ``flex.slot.*`` scope); None without a
    table."""
    if table is None:
        return None
    ms = table["ms_per_slot"]
    if layer == "admission":
        return ms.get(ADMISSION, 0.0)
    if layer == "slot_pipeline":
        return sum(v for s, v in ms.items() if s.startswith(SLOT_PREFIX))
    raise ValueError(f"unknown layer {layer!r}")


def read_containers(trace_dir) -> list:
    """Per device, the container events (``while``, ``conditional``,
    ``call``) that ``tracing.read`` leaves out of the busy union."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if not (plane.name.startswith(DEVICE_PREFIX)
                and plane.name[len(DEVICE_PREFIX):].isdigit()):
            continue
        events = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                events += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                           for ev in line.events
                           if opcode(ev.name) in CONTAINER_OPS]
        out.append(events)
    return out
