"""Batched serving example: prefill + greedy decode with KV cache across a
request batch, with per-phase throughput — the serving-path counterpart of
the decode_32k / long_500k dry-run cells.

  PYTHONPATH=src python examples/serve_batched.py --arch mixtral-8x7b

REPRO_SMOKE=1 shrinks the run (reduced model, batch 2, 16-token prompts,
4 new tokens) so the tier-1 smoke test can execute the full serve path —
prefill, decode loop, KV cache — in ~15s on the CPU container instead of
compile-checking only.
"""

import argparse
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "src"))

from repro.env import smoke_mode

SMOKE = smoke_mode()

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--batch", type=int, default=2 if SMOKE else 4)
    ap.add_argument("--prompt-len", type=int, default=16 if SMOKE else 64)
    ap.add_argument("--max-new", type=int, default=4 if SMOKE else 16)
    args = ap.parse_args()
    # the serving driver is a first-class launcher; this example invokes it
    # the way an operator would
    sys.exit(subprocess.call([
        sys.executable, "-m", "repro.launch.serve",
        "--arch", args.arch, "--reduced",
        "--batch", str(args.batch),
        "--prompt-len", str(args.prompt_len),
        "--max-new", str(args.max_new),
    ], env=dict(os.environ, PYTHONPATH=os.pathsep.join(
        p for p in (f"{REPO}/src", os.environ.get("PYTHONPATH")) if p))))
