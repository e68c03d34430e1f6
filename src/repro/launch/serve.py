"""Real-model serving driver: Flex admission over live KV caches.

Each replica is a slot-batched decode instance of the (reduced) model: a
cache pytree with ``slots`` sequences.  On admission the engine's hook runs
a single-request prefill and writes it into the replica's slot via
dynamic-update-slice "KV surgery"; every engine step runs one REAL jitted
decode step per non-empty replica.  Flex (usage-based admission + penalty
feedback) decides which replica takes each request — the paper's scheduler
running over actual accelerator memory.

  PYTHONPATH=src python -m repro.launch.serve --policy flex-f --requests 64
  PYTHONPATH=src python -m repro.launch.serve --policy least-fit --requests 64
  # open-loop at production rate (arrival patterns from traces.generator):
  PYTHONPATH=src python -m repro.launch.serve --stream burst --rate 2 \
      --steps 200 --mode wavefront
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_smoke_config
from repro.models.model import build_model, init_cache
from repro.serving.engine import (ADMISSION_MODES, EngineConfig, Request,
                                  ServeEngine)
from repro.serving.stream import RequestStream, StreamConfig
from repro.traces.generator import ARRIVAL_PATTERNS


class RealModelBackend:
    """Slot-batched decode backend for one model across R replicas."""

    def __init__(self, arch: str, n_replicas: int, slots: int,
                 max_seq: int, seed: int = 0):
        self.cfg = get_smoke_config(arch)
        self.model = build_model(self.cfg, remat=False)
        self.params = self.model.init(jax.random.PRNGKey(seed))
        self.slots = slots
        self.max_seq = max_seq
        self.caches = [init_cache(self.cfg, slots, max_seq)
                       for _ in range(n_replicas)]
        self.tokens = [jnp.zeros((slots, 1), jnp.int32)
                       for _ in range(n_replicas)]
        self.slot_of: Dict[int, int] = {}          # rid -> slot
        self.free: List[List[int]] = [list(range(slots))
                                      for _ in range(n_replicas)]
        self._prefill = jax.jit(self.model.prefill)
        self._decode = jax.jit(self.model.decode)

    # ---- engine hooks ----
    def on_admit(self, req: Request):
        r = req.replica
        if not self.free[r]:
            return
        slot = self.free[r].pop()
        self.slot_of[req.rid] = slot
        prompt = jnp.asarray(
            np.random.default_rng(req.rid).integers(
                0, self.cfg.vocab_size, (1, req.prompt_len)), jnp.int32)
        logits, cache1 = self._prefill(self.params, {"tokens": prompt})
        # KV surgery: write the single-request cache into the slot
        cache = self.caches[r]
        for key in cache1:
            if key == "len":
                continue
            src, dst = cache1[key], cache[key]
            if isinstance(src, tuple):  # hybrid shared cache
                new = []
                for s, d in zip(src, dst):
                    pad = [(0, 0)] * s.ndim
                    pad[2] = (0, d.shape[2] - s.shape[2])
                    s = jnp.pad(s, pad)
                    new.append(jax.lax.dynamic_update_slice_in_dim(
                        d, s.astype(d.dtype), slot, axis=1))
                cache[key] = tuple(new)
            else:
                if src.ndim >= 3 and src.shape[2] != dst.shape[2] \
                        and key in ("k", "v"):
                    pad = [(0, 0)] * src.ndim
                    pad[2] = (0, dst.shape[2] - src.shape[2])
                    src = jnp.pad(src, pad)
                cache[key] = jax.lax.dynamic_update_slice_in_dim(
                    dst, src.astype(dst.dtype), slot, axis=1)
        self.tokens[r] = self.tokens[r].at[slot, 0].set(
            jnp.argmax(logits[0]).astype(jnp.int32))

    def on_evict(self, req: Request):
        slot = self.slot_of.pop(req.rid, None)
        if slot is not None:
            self.free[req.replica].append(slot)

    def decode_fn(self, replica: int, reqs) -> float:
        t0 = time.time()
        cache = self.caches[replica]
        logits, new_cache = self._decode(self.params, cache,
                                         self.tokens[replica])
        self.caches[replica] = new_cache
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        self.tokens[replica] = nxt
        for r in reqs:
            if r.done and r.rid in self.slot_of:
                self.free[replica].append(self.slot_of.pop(r.rid))
        return time.time() - t0


def make_workload(n: int, seed: int = 0):
    """Requests that over-declare max_tokens, like Google-trace users."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        true = int(rng.integers(4, 40))
        declared = int(true * rng.uniform(1.5, 4.0))   # ~45% usage/request
        out.append(Request(rid=i, prompt_len=int(rng.integers(8, 24)),
                           max_tokens=declared, true_tokens=true))
    return out


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--policy", default="flex-f",
                    help="a repro.api.registry policy name (flex-f, "
                         "least-fit, flex-priority, best-fit-usage, ...)")
    ap.add_argument("--mode", choices=ADMISSION_MODES, default="wavefront",
                    help="admission execution mode (EngineConfig"
                         ".admission_mode)")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--budget", type=int, default=512)
    ap.add_argument("--stream", choices=ARRIVAL_PATTERNS, default=None,
                    help="drive open-loop from this arrival pattern instead "
                         "of a pre-filled queue")
    ap.add_argument("--rate", type=float, default=2.0,
                    help="mean arrivals per engine step (with --stream)")
    args = ap.parse_args()

    backend = RealModelBackend(args.arch, args.replicas, args.slots,
                               max_seq=256)
    cfg = EngineConfig(
        n_replicas=args.replicas, kv_budget_tokens=args.budget,
        policy=args.policy,
        max_active_per_replica=args.slots,
        admission_mode=args.mode)
    eng = ServeEngine(cfg, decode_fn=backend.decode_fn)
    eng.on_admit = backend.on_admit
    eng.on_evict = backend.on_evict

    t0 = time.time()
    if args.stream:
        # Open-loop: arrivals pushed at --rate per step; sized for the
        # smoke model's short sequences.
        stream = RequestStream(
            StreamConfig(pattern=args.stream, mean_rate=args.rate,
                         prompt_mean=12, max_tokens_mean=24),
            horizon=args.steps)
        stats = stream.drive(eng, steps=args.steps)
        args.requests = stream.submitted
    else:
        for req in make_workload(args.requests):
            eng.submit(req)
        stats = eng.run(args.steps)
    wall = time.time() - t0
    print(f"policy={args.policy} replicas={args.replicas} "
          f"budget={args.budget}tok")
    print(f"finished {stats.finished}/{args.requests} admitted "
          f"{stats.admitted} evict_events {stats.evicted_events}")
    print(f"mean util {np.mean(stats.util_series):.3f} "
          f"final QoS {stats.qos_series[-1]:.4f} "
          f"final P {stats.penalty_series[-1]:.3f}")
    print(f"tokens/s {stats.tokens_generated / wall:.1f} (real decode steps)")


if __name__ == "__main__":
    main()
