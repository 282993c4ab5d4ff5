"""The benchmark's own tests: CPU only, tiny sizes.  They lie outside the
repository's tier-1 collection (pytest.ini collects `tests/`); run them
with `JAX_PLATFORMS=cpu python -m pytest bench/tests -q`."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
