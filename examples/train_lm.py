"""End-to-end training example: a ~100M-param mamba2 variant for a few
hundred steps with checkpoint/restart, on CPU.

  PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""
import argparse
import dataclasses

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.launch.train import train


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    # ~100M-param member of the mamba2 family (CPU-trainable)
    cfg = dataclasses.replace(
        get_config("mamba2-370m"), name="mamba2-100m",
        n_layers=12, d_model=512, vocab_size=8192, dtype="float32")

    import repro.configs as configs

    # register it so the train driver can resolve it
    class _Mod:
        CONFIG = cfg

        @staticmethod
        def smoke_config():
            return cfg

    import sys
    sys.modules["repro.configs.mamba2_100m"] = _Mod
    configs.ARCH_IDS.append("mamba2-100m")

    _, _, losses = train("mamba2-100m", smoke=False, steps=args.steps,
                         batch=8, seq=256, ckpt_dir=args.ckpt_dir,
                         resume=args.resume, ckpt_every=50, log_every=10,
                         lr=3e-4)
    print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f} over "
          f"{len(losses)} steps")


if __name__ == "__main__":
    main()
