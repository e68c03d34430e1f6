"""Profiler control and the reduction of a trace to numbers.

The JAX profiler writes one ``.xplane.pb`` per run.  On a TPU the device
plane (``/device:TPU:<i>``) holds a line ``XLA Ops``, one event per
executed HLO instruction named by its text (``%name = shape opcode(...)``),
and a line ``XLA Modules``, one event per executed program.  The host
plane (``/host:CPU``) holds the host spans named ``chipbench.*``.

Busy time is the union of the intervals of leaf operations: control flow
(``while``, ``conditional``, ``call``) spans its body and is left out, so
the gaps between the small operations of a loop count as idle.  Device
and host events are on one clock only up to the profiler's alignment
(about a millisecond), so an idle gap is given to a host span only to
that precision; a gap that no span covers goes to the device program
(module) it lies in.
"""
from __future__ import annotations

import glob
import re
import threading
from collections import defaultdict

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CONTAINER_OPS = frozenset({"while", "conditional", "call"})
SPAN_PREFIX = "chipbench."
TRACE_START, TRACE_END = "chipbench.trace_start", "chipbench.trace_end"
_OPCODE = re.compile(r"\s([a-z][\w\-.]*)\(")
_STEM = re.compile(r"^%([^\s=]+?)(\.\d+)?\s=")


def opcode(hlo_text: str) -> str:
    """The opcode of an instruction's text, e.g. ``fusion``, ``while``."""
    rhs = hlo_text.split(" = ", 1)[-1]
    m = _OPCODE.search(" " + rhs)
    return m.group(1) if m else ""


def stem(hlo_text: str) -> str:
    """The instruction's name without its numeric suffix."""
    m = _STEM.match(hlo_text)
    return m.group(1) if m else hlo_text.split(" ", 1)[0]


def union(starts, ends):
    """Merged, sorted intervals covering [starts[i], ends[i])."""
    if len(starts) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(starts, kind="stable")
    s = np.asarray(starts, float)[order]
    e = np.asarray(ends, float)[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.append(reach[idx[1:] - 1], reach[-1])


def covered(us, ue, lo, hi) -> float:
    """Length of [lo, hi) covered by merged intervals (us, ue)."""
    return float(np.sum(np.clip(np.minimum(ue, hi) - np.maximum(us, lo),
                                0, None)))


class Reduced:
    """What the metrics read from one trace: per device its leaf ops and
    programs, and the ``chipbench.*`` host spans; all times in ns."""

    def __init__(self, devices, spans):
        self.devices = devices          # [{"ops": [...], "modules": [...]}]
        self.spans = spans              # name -> [(start, end)]
        lo = [s for s, _ in spans.get(TRACE_START, [])]
        hi = [e for _, e in spans.get(TRACE_END, [])]
        if lo and hi:
            self.lo, self.hi = min(lo), max(hi)
        else:   # no markers: the extent of the device's operations
            ends = [(o[1], o[2]) for d in devices for o in d["ops"]]
            self.lo = min(s for s, _ in ends) if ends else 0.0
            self.hi = max(e for _, e in ends) if ends else 0.0
        for d in devices:
            ops = [o for o in d["ops"] if o[2] > self.lo and o[1] < self.hi]
            d["ops"] = ops
            d["busy"] = union([o[1] for o in ops], [o[2] for o in ops])

    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the devices."""
        if not self.devices:
            return 0.0
        return float(np.mean([covered(*d["busy"], self.lo, self.hi)
                              for d in self.devices])) / 1e9

    def idle_share(self):
        w = self.window_s()
        if w <= 0 or not self.devices:
            return None
        return 1.0 - self.busy_s() / w

    def top_ops(self, n=10):
        tot = defaultdict(float)
        for d in self.devices:
            for name, s, e in d["ops"]:
                tot[stem(name)] += (e - s) / 1e9 / len(self.devices)
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_by_span(self, n=10):
        """Idle seconds in the window by the innermost host span open
        at the middle of each gap ("outside spans" where none is); spans
        with the shorter mean length count as the inner ones."""
        named = []
        for name, v in self.spans.items():
            if name in (TRACE_START, TRACE_END) or not v:
                continue
            s = np.array([a for a, _ in v], float)
            e = np.array([b for _, b in v], float)
            named.append((float(np.mean(e - s)), name, union(s, e)))
        named.sort()
        tot = defaultdict(float)
        for d in self.devices:
            us, ue = d["busy"]
            gs = np.maximum(np.concatenate([[self.lo], ue]), self.lo)
            ge = np.minimum(np.concatenate([us, [self.hi]]), self.hi)
            keep = ge > gs
            gs, ge = gs[keep], ge[keep]
            mid = (gs + ge) / 2
            left = np.ones(len(mid), bool)
            for _, name, (ss, se) in named:
                i = np.searchsorted(ss, mid, side="right") - 1
                inside = left & (i >= 0) & (mid < se[np.maximum(i, 0)])
                tot[name] += float(np.sum((ge - gs)[inside])) / 1e9
                left &= ~inside
            # no host span open (a traced run that ends inside a long
            # call): the device program the gap lies in, if any
            for mname, ms, me in d["modules"]:
                if not left.any():
                    break
                inside = left & (mid >= ms) & (mid < me)
                if inside.any():
                    label = "in " + mname.split("(", 1)[0]
                    tot[label] += float(np.sum((ge - gs)[inside])) / 1e9
                    left &= ~inside
            tot["outside spans"] += float(np.sum((ge - gs)[left])) / 1e9
        k = max(len(self.devices), 1)
        return sorted(([name, v / k] for name, v in tot.items() if v > 0),
                      key=lambda kv: -kv[1])[:n]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_by_span()}


def read(path: str) -> Reduced:
    """Reduce one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = [], defaultdict(list)
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX) and plane.name[
                len(DEVICE_PREFIX):].isdigit():
            d = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        if opcode(ev.name) not in CONTAINER_OPS:
                            d["ops"].append((ev.name, ev.start_ns,
                                             ev.start_ns + ev.duration_ns))
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        d["modules"].append((ev.name, ev.start_ns,
                                             ev.start_ns + ev.duration_ns))
            devices.append(d)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans[ev.name].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    return Reduced(devices, dict(spans))


def reduce(trace_dir) -> Reduced:
    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    return read(files[-1])


def _options():
    from jax.profiler import ProfileOptions

    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


class Tracer:
    """The profiler over a part of the window that the front end chooses.

    ``start(after, seconds, label)`` returns at once; a thread of its own
    waits ``after`` seconds, starts the profiler, holds a host span
    ``label`` open while it records (so the idle gaps of a long device
    call that began before the trace are attributed to it), and stops it
    after ``seconds`` or at ``close()``, whichever comes first.  A study
    is one long device call, so the window's own thread is inside it
    while the trace starts and stops.
    """

    def __init__(self, trace_dir):
        self.trace_dir = str(trace_dir)
        self._halt = threading.Event()
        self._recording = threading.Event()
        self._thread = None

    def start(self, after: float, seconds: float, label: str):
        self._thread = threading.Thread(
            target=self._record, args=(after, seconds, label), daemon=True)
        self._thread.start()

    def _record(self, after, seconds, label):
        import jax

        if self._halt.wait(after):
            return
        jax.profiler.start_trace(self.trace_dir, profiler_options=_options())
        with jax.profiler.TraceAnnotation(TRACE_START):
            pass
        self._recording.set()
        with jax.profiler.TraceAnnotation(label):
            self._halt.wait(seconds)
        with jax.profiler.TraceAnnotation(TRACE_END):
            pass
        jax.profiler.stop_trace()

    def started(self) -> bool:
        """Whether the profiler has started recording."""
        return self._recording.is_set()

    def done(self) -> bool:
        """Whether a recording was started and has ended."""
        return self._thread is not None and not self._thread.is_alive()

    def close(self):
        """Stops the recording (or its start, where it is still to come)
        and waits until the profiler has written the trace."""
        self._halt.set()
        if self._thread is not None:
            self._thread.join()
