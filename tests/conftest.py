import os
import sys

# Smoke tests and benches must see the REAL device count (1 CPU device).
# Only launch/dryrun.py forces 512 host devices — and only in its own process.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
