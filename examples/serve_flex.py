"""Serving example: Flex vs reserve admission over REAL model decode.

Each replica holds a live slot-batched KV cache of a reduced stablelm;
requests over-declare max_tokens (like Google-trace users over-request).
Flex admission packs ~2-3x more concurrent requests at the same QoS.

  PYTHONPATH=src python examples/serve_flex.py

Each policy runs in its own child process; this parent never imports
JAX, so a child can hold the accelerator.  The children inherit the
environment, ``JAX_COMPILATION_CACHE_DIR`` included.
"""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main():
    for policy in ("reserve", "flex"):
        print(f"=== policy: {policy} ===", flush=True)
        subprocess.run(
            [sys.executable, "-m", "repro.launch.serve",
             "--policy", policy, "--requests", "48", "--steps", "100",
             "--budget", "384", "--slots", "12"],
            env={**os.environ, "PYTHONPATH": str(ROOT / "src")},
            check=True)


if __name__ == "__main__":
    main()
