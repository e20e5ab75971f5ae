import os
import sys

# The benchmark's tests run on the CPU; the harness itself refuses to.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def pytest_pycollect_makemodule(module_path, parent):
    """Put the harness's directory first again before each of these test
    modules is imported: a test elsewhere in the suite puts ``benchmarks/``,
    which has a ``run.py`` of its own, at the head of ``sys.path`` while the
    suite is collected."""
    if sys.path[0] != BENCH:
        if BENCH in sys.path:
            sys.path.remove(BENCH)
        sys.path.insert(0, BENCH)
