"""Tier-1 entry for the benchmark's own tests (``benchmarks/tests``, CPU
rehearsals at tiny sizes; ROADMAP D2): every case of its five modules is
collected here under a class of its module's name, so that the tier-1
command (``pytest tests/``) runs them and counts each.  The modules stay
where the benchmark keeps them and still run on their own
(``python -m pytest benchmarks/tests``)."""
import importlib
import importlib.util
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_TESTS = os.path.join(ROOT, "benchmarks", "tests")

# the benchmark tests' own conftest (the name ``conftest`` is this
# directory's): it puts benchmarks/ on the path and gives every test's
# runs a scratch of their own
_spec = importlib.util.spec_from_file_location(
    "benchmarks_tests_conftest", os.path.join(BENCH_TESTS, "conftest.py"))
_conftest = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_conftest)
scratch_of_its_own = _conftest.scratch_of_its_own


def _cases_of(module_name: str) -> type:
    module = importlib.import_module(module_name)
    cases = {name: staticmethod(obj) for name, obj in vars(module).items()
             if name.startswith("test_") and callable(obj)}
    return type(f"Test_{module_name}", (), cases)


from test_benchmark import fresh_counters  # noqa: E402,F401  (autouse there, so here)

TestBenchmark = _cases_of("test_benchmark")
TestSpanReaders = _cases_of("test_span_readers")
TestTree = _cases_of("test_tree")
TestMovable = _cases_of("test_movable")
TestResident = _cases_of("test_resident")
