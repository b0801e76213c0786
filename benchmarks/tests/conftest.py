"""The benchmark's own tests run on the CPU backend, at tiny sizes."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
TESTS = os.path.dirname(os.path.abspath(__file__))  # holds faults/
HERE = os.path.dirname(TESTS)
for p in (TESTS, HERE, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def scratch_of_its_own(monkeypatch, tmp_path):
    """A run empties its scratch, ``cache/work/<cell>`` (the profiler's
    trace), when it starts and when it ends; under xdist two test files
    rehearse one cell at once, and one took the other's trace away.  Here
    each test's runs keep their scratch in the test's own directory."""
    import run as bench

    init = bench.Run.__init__

    def redirected(self, args, manifest):
        init(self, args, manifest)
        self.work_dir = str(tmp_path / "work")
        self.trace_dir = os.path.join(self.work_dir, "trace")

    monkeypatch.setattr(bench.Run, "__init__", redirected)
