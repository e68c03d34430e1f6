"""Paper-scale cluster simulation (Fig. 6-9 pipeline) with CSV output.

Runs every registered placement policy — the four paper schedulers plus
the registry extensions (best-fit-usage, flex-priority) — through the
``Experiment`` API.  Reduced by default; --full runs the 4000-node / 24 h /
~700k-task setup from the paper's §5.1 (several minutes on CPU).

  PYTHONPATH=src python examples/cluster_sim.py [--full] [--out out.csv]
"""
import argparse
import sys
import time

from repro.api import Experiment, list_policies
from repro.compile_cache import enable_compile_cache
from repro.core import SimConfig
from repro.traces import generate_calibrated


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--offered", type=float, default=1.6)
    ap.add_argument("--policies", nargs="*", default=None,
                    help="registry names (default: all registered)")
    args = ap.parse_args()

    if args.full:
        cfg = SimConfig(n_nodes=4000, n_slots=288,
                        arrivals_per_slot=4096, retry_capacity=1024)
    else:
        cfg = SimConfig(n_nodes=400, n_slots=96,
                        arrivals_per_slot=1024, retry_capacity=256)
    ts = generate_calibrated(0, cfg.n_nodes, cfg.n_slots, args.offered)
    print(f"# nodes={cfg.n_nodes} slots={cfg.n_slots} tasks={ts.num_tasks}",
          file=sys.stderr)
    lines = ["method,usage_cpu,usage_mem,request_cpu,admitted_frac,"
             "qos_mean,violation_frac,norm_std_mem,final_penalty,wall_s"]
    for name in (args.policies or list_policies()):
        t0 = time.time()
        s = Experiment(ts, cfg, policy=name).summarize(0.99)
        lines.append(
            f"{name},{s['avg_usage_cpu']:.4f},{s['avg_usage_mem']:.4f},"
            f"{s['avg_request_cpu']:.4f},{s['admitted_frac']:.4f},"
            f"{s['qos_mean']:.4f},{s['qos_violation_frac']:.4f},"
            f"{s['mean_norm_std_mem']:.4f},{s['final_penalty']:.2f},"
            f"{time.time() - t0:.1f}")
        print(lines[-1], file=sys.stderr)
    text = "\n".join(lines)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    else:
        print(text)


if __name__ == "__main__":
    main()
