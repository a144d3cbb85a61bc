import os
import sys

# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see
# ONE device; only launch/dryrun.py (and the subprocess test) force 512.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# Programs compiled by tests (CPU) stay out of the checkout's persistent
# compile cache (repro.chip); the subprocesses tests start inherit this.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
