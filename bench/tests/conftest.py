"""CPU tests of the benchmark harness: ``pytest bench/tests``."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def pytest_configure(config):
    # compile nothing into the checkout's cache from the tests
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
