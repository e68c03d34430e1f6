"""The benchmark's harness, driven by ``BENCHMARK.json`` and data files.

A cell names a configuration and a traffic mix.  The harness finds them
by name: ``configs/<config>.json`` (which names its front end),
``traffic/<mix>.json``, ``frontends/<frontend>.py`` and, for each
per-layer metric, ``metrics/<metric>.py``.  Adding a cell, a
configuration, a mix or a metric is adding files and entries; nothing
here changes.

A front end module exposes ``Session(config, mix, seed)``, whose
constructor is the set-up, with ``window(seconds, tracer)``,
``release()`` and ``check(kept, limits)``; the window returns the
end-to-end metrics it measured, and in a traced run starts ``tracer``
(``tracing.Tracer``) over the part of the window it chooses.  A metric
module exposes ``read(ctx)``, which returns a number or None when the
run holds nothing to read.  So a cell reports the end-to-end metrics
its front end measures and the per-layer metrics whose readers find
something, with no list of cells to edit.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
OUT = HERE / "_out"


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(bench: dict, workload: str) -> SimpleNamespace:
    """The cell's entry, configuration, mix, front end and metrics."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; one of "
                       f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    mix = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    frontend = HERE / "frontends" / f"{config['frontend']}.py"

    for m in bench["per_layer"]:
        if not (HERE / "metrics" / f"{m['name']}.py").is_file():
            raise FileNotFoundError(f"no reader metrics/{m['name']}.py")
    if not frontend.is_file():
        raise FileNotFoundError(f"no front end {frontend}")
    return SimpleNamespace(cell=cell, config=config, mix=mix,
                           frontend=frontend, e2e=bench["end_to_end"],
                           per_layer=bench["per_layer"])


def named_for(metrics: list, workload: str) -> set:
    """Names of the metrics whose ``workloads`` list names the cell: a
    run that lacks one of them is not correct."""
    return {m["name"] for m in metrics if workload in m.get("workloads", ())}


def fail(msg: str, code: int) -> int:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    return code


def parse_args(argv):
    ap = argparse.ArgumentParser(prog="chipbench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def devices_or_none(chips: int):
    """The cell's TPU devices, or None where JAX finds too few."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError:
        return None
    if not devs or devs[0].platform != "tpu" or len(devs) < chips:
        return None
    return devs[:chips]


def enable_cache():
    """The program's persistent compile cache, at its fixed path inside
    the checkout (or where JAX_COMPILATION_CACHE_DIR says), keeping every
    program so that a second run loads all of them."""
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts backend compiles while open, so a compile inside the window
    shows."""

    def __enter__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def main(argv, t_start: float) -> int:
    args = parse_args(argv)
    try:
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
        run = resolve(bench, args.workload)
    except (OSError, KeyError, ValueError) as e:
        return fail(f"cannot resolve the cell: {e}", 2)
    chips = int(run.cell["chips"])
    devs = devices_or_none(chips)
    if devs is None:
        return fail(f"needs {chips} TPU chip(s); JAX finds none or fewer", 3)
    print(f"chipbench: {chips} chip(s) found after "
          f"{time.perf_counter() - t_start:.3f} s", file=sys.stderr,
          flush=True)
    sys.path.insert(0, str(ROOT / "src"))
    enable_cache()
    line = run_cell(run, args, devs, t_start)
    print(json.dumps(line), flush=True)
    return 0


def run_cell(run, args, devs, t_start: float) -> dict:
    """Set-up, window, trace reduction, check; the result line as a dict.

    ``main`` calls it once it has found the cell's chips; a test may call
    it with other devices and a shrunken ``run.config``.
    """
    frontend = load_module(run.frontend, "chipbench_frontend")
    session = frontend.Session(run.config, run.mix, args.seed)
    setup_s = time.perf_counter() - t_start
    print(f"chipbench: {args.workload} seed={args.seed} set-up "
          f"{setup_s:.3f} s", file=sys.stderr, flush=True)

    trace_dir = OUT / "trace" / args.workload
    tracer = None
    if args.trace:
        from tracing import Tracer

        shutil.rmtree(trace_dir, ignore_errors=True)
        tracer = Tracer(trace_dir)
    with CompileCounter() as counter:
        result = session.window(args.seconds, tracer)
    compiles = counter.n
    reduced = None
    if tracer is not None:
        from tracing import reduce

        tracer.close()
        reduced = reduce(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    if compiles:
        print(f"chipbench: WARNING {compiles} compile(s) inside the window",
              file=sys.stderr, flush=True)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak(devs)}

    kept = session.release()
    checks = session.check(kept, run.config["check_limits"])
    correct = all(v is not None and v <= lim for _, v, lim in checks)

    metrics = {}
    if reduced is not None:
        ctx = SimpleNamespace(trace=reduced, frontend=run.frontend.stem)
        for m in run.per_layer:
            reader = load_module(HERE / "metrics" / f"{m['name']}.py",
                                 "chipbench_metric")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced.busy_s()
        device["window_s"] = reduced.window_s()
        wanted = named_for(run.per_layer, args.workload)
    else:
        values = dict(result["e2e"], setup_s=setup_s)
        for m in run.e2e:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
        wanted = named_for(run.e2e, args.workload) | {"setup_s"}
        if len(metrics) < 2:
            correct = False
            print("chipbench: the front end measured no end-to-end metric "
                  "that BENCHMARK.json names", file=sys.stderr)
    for name in sorted(wanted - set(metrics)):
        correct = False
        print(f"chipbench: no reading of {name}", file=sys.stderr)
    info = dict(result["info"], compiles_in_window=compiles)
    line = {"correct": bool(correct), "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics,
            "device": device, "info": info}
    if reduced is not None:
        line["breakdown"] = reduced.breakdown()
    line["checks"] = {name: {"value": v, "limit": lim}
                      for name, v, lim in checks}
    for name, v, lim in checks:
        ok = v is not None and v <= lim
        print(f"check {name} value={v!r} limit={lim!r} "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr, flush=True)
    return line
