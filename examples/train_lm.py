"""End-to-end driver: train a ~100M-param LM for a few hundred steps.

  PYTHONPATH=src python examples/train_lm.py [--steps 300] [--arch olmo-1b]

Uses the full production train loop (launch/train.py): sharded step,
AdamW + cosine schedule, checkpoint/resume, optional REX-delta gradient
compression.  The default config is a width-reduced olmo family member
sized to run on CPU; on a pod, drop --reduced and set --mesh.
"""
import sys

from repro.compile_cache import enable_compile_cache
from repro.launch import train

if __name__ == "__main__":
    sys.argv = [sys.argv[0], "--arch", "olmo-1b", "--reduced",
                "--steps", "300", "--seq-len", "128",
                "--global-batch", "16", "--lr", "3e-3",
                "--ckpt-every", "100",
                "--compression", "delta"] + sys.argv[1:]
    enable_compile_cache()
    train.main()
