"""End-to-end LM training driver: SQL-selected corpus -> columnar pipeline ->
train a reduced qwen2.5 config for a few hundred steps with checkpointing
and a simulated preemption + restart.

    PYTHONPATH=src python examples/train_lm.py [--steps 300]

(Thin wrapper over repro.launch.train; on TPU hardware the same driver
takes --arch qwen2.5-3b and the production mesh.)
"""

import sys

from repro.compile_cache import enable_compile_cache
from repro.launch.train import main

if __name__ == "__main__":
    enable_compile_cache()
    sys.argv = [sys.argv[0], "--arch", "qwen2.5-3b-smoke",
                "--steps", "300", "--seq-len", "64", "--batch", "16",
                "--lr", "3e-3", "--ckpt-every", "100",
                "--simulate-preemption", "150",
                "--ckpt-dir", "/tmp/repro_example_ckpt"] + sys.argv[1:]
    main()
