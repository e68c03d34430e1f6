"""Quickstart: reproduce the paper's headline result in ~1 minute on CPU.

Runs the four schedulers (LeastFit, Oversub, FlexF, FlexL) through the
``repro.api.Experiment`` front-end on a reduced Google-trace-twin workload
and prints the Fig. 6/7 summary: Flex matches Oversub's utilization at
LeastFit's QoS.

  PYTHONPATH=src python examples/quickstart.py
"""
from repro.api import Experiment
from repro.compile_cache import enable_compile_cache
from repro.core import SimConfig
from repro.traces import generate_calibrated


def main():
    enable_compile_cache()
    cfg = SimConfig(n_nodes=200, n_slots=96, arrivals_per_slot=1024,
                    retry_capacity=256)
    ts = generate_calibrated(0, cfg.n_nodes, cfg.n_slots, offered_load=1.6)
    print(f"cluster: {cfg.n_nodes} nodes x {cfg.n_slots} slots, "
          f"{ts.num_tasks} tasks (offered ~1.6x capacity)\n")
    print(f"{'method':14s} {'util':>6s} {'admitted':>9s} {'QoS':>7s} "
          f"{'viol%':>6s} {'final P':>8s}")
    summaries = {}
    for name in ("least-fit", "oversub", "flex-f", "flex-l"):
        s = Experiment(ts, cfg, policy=name).summarize(0.99)
        summaries[name] = s
        print(f"{name:14s} {s['avg_usage_cpu']:6.3f} "
              f"{s['admitted_frac']:9.3f} {s['qos_mean']:7.4f} "
              f"{100 * s['qos_violation_frac']:6.1f} "
              f"{s['final_penalty']:8.2f}")
    base, flex = summaries["least-fit"], summaries["flex-f"]
    print(f"\nFlexF vs LeastFit: "
          f"{flex['avg_usage_cpu'] / base['avg_usage_cpu']:.2f}x "
          f"utilization, "
          f"{flex['avg_request_cpu'] / base['avg_request_cpu']:.2f}x "
          f"admitted requests  (paper: 1.6x / 1.74x)")


if __name__ == "__main__":
    main()
