"""Batched serving example: prefill + KV-cache decode on a reduced config.

    PYTHONPATH=src python examples/serve_lm.py
"""

import sys

from repro.compile_cache import enable_compile_cache
from repro.launch.serve import main

if __name__ == "__main__":
    enable_compile_cache()
    sys.argv = [sys.argv[0], "--arch", "yi-9b-smoke", "--batch", "8",
                "--prompt-len", "32", "--new-tokens", "48"] + sys.argv[1:]
    main()
