"""CPU rehearsals of the benchmark at tiny sizes (``--rehearsal``): the
manifest and its data files, every cell end to end, each cell's control
and planted faults (``correct`` must come out false), the trace
reduction on a recorded v5e trace, the byte counts from shapes.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
from __future__ import annotations

import importlib
import json
import os
import shutil
import subprocess
import sys

import pytest

import bytes_model
import run as bench
import trace_reduce

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MANIFEST = bench.load_json(bench.ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def driver_of(cell: dict) -> str:
    return bench.load_json(HERE, "traffic", cell["traffic"] + ".json")["driver"]


def faults_of(driver: str):
    """``faults/<driver>.py`` (``FAULTS``, ``plant(monkeypatch, fault)``):
    the faults a cell of that driver can have and how to plant one under
    its timed path; None for a driver that has no such file yet."""
    try:
        return importlib.import_module(f"faults.{driver}")
    except ModuleNotFoundError as e:
        if e.name not in ("faults", f"faults.{driver}"):
            raise
        return None


@pytest.fixture(autouse=True)
def fresh_counters():
    """The obs registry is the process's: a counter that one test moved
    must not fail the next."""
    from loro_tpu.obs import metrics as obs

    obs.reset()


def run_cell(capsys, cell: str, *extra: str, seed: int = 2147483659,
             seconds: float = 0.5):
    rc = bench.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     str(seconds), "--rehearsal", *extra])
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(lines[-1])


def test_manifest_names_only_what_exists():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    assert MANIFEST["command"][1].startswith(MANIFEST["paths"][0] + "/")
    assert "setup_s" in e2e
    for c in configs.values():
        body = bench.load_json(bench.ROOT, c["file"])
        assert set(c["reduced"]) == set(body["reduced"])
        assert body["guarantees"]
    rates = [m for m in MANIFEST["end_to_end"] if m["name"] != "setup_s"]
    for w in MANIFEST["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        driver = driver_of(w)
        importlib.import_module(f"drivers.{driver}")
        assert faults_of(driver), f"no benchmarks/tests/faults/{driver}.py"
        # setup_s alone says nothing of the window: every cell has a rate
        assert any(w["name"] in m.get("workloads", CELLS) for m in rates)
    for m in MANIFEST["per_layer"]:
        spec = bench.load_json(HERE, "layer_metrics", m["name"] + ".json")
        assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]
        assert m["moves"] in e2e and set(m["workloads"]) <= set(CELLS)
        importlib.import_module(f"readers.{spec['reader']}")


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_prints_the_contracts_line(capsys, cell, trace):
    rc, res = run_cell(capsys, cell, "--trace", str(trace))
    assert rc == 0 and res["correct"] is True and res["rehearsal"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "compared"
    assert all(v["value"] <= v["limit"] for v in res["compared"].values())
    assert res["device"]["platform"] == "cpu"
    # a CPU number never stands under a device metric's name
    assert all(k.startswith("cpu_rehearsal.") for k in res["metrics"])
    if trace:  # no device plane in a CPU trace: its readers return nothing
        assert "busy_s" not in res["device"]
        assert not any("roofline" in k or "idle" in k for k in res["metrics"])
    else:
        names = {k.split(".", 1)[1] for k in res["metrics"]}
        assert "setup_s" in names and len(names) >= 2


@pytest.mark.parametrize("cell", CELLS)
def test_control_comes_out_not_correct(capsys, cell):
    rc, res = run_cell(capsys, cell, "--control")
    assert rc == 1 and res["correct"] is False


def _fault_cases():
    """Every cell under every fault its driver's ``faults`` module names;
    a driver without one gives ONE case, which fails and names the file."""
    missing = set()
    for w in MANIFEST["workloads"]:
        driver = driver_of(w)
        module = faults_of(driver)
        if module is None and driver not in missing:
            missing.add(driver)
            yield pytest.param(w["name"], driver, None,
                               id=f"{w['name']}-no_faults_module")
        for fault in getattr(module, "FAULTS", ()):
            yield pytest.param(w["name"], driver, fault,
                               id=f"{w['name']}-{fault}")


@pytest.mark.parametrize("cell,driver,fault", list(_fault_cases()))
def test_planted_fault_makes_correct_false(capsys, monkeypatch, cell, driver,
                                           fault):
    if fault is None:
        pytest.fail(f"driver {driver!r} has no planted faults: add "
                    f"benchmarks/tests/faults/{driver}.py with FAULTS and "
                    "plant(monkeypatch, fault)")
    faults_of(driver).plant(monkeypatch, fault)
    rc, res = run_cell(capsys, cell)
    assert rc == 1 and res["correct"] is False
    assert any(v["value"] > v["limit"] for v in res["compared"].values())


def test_a_driver_without_a_faults_module_is_one_failing_case(tmp_path):
    """A copy of the benchmark whose manifest has one more cell, of a
    driver that has no ``faults`` module: the test module still collects,
    the six cases are there under their ids, and ONE case fails, naming
    the file to add."""
    shutil.copytree(HERE, tmp_path / "benchmarks", ignore=shutil.ignore_patterns(
        "cache", "__pycache__", ".pytest_cache", "testdata"))
    third = {**MANIFEST["workloads"][-1], "name": "b4_import.third",
             "traffic": "third"}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(
        {**MANIFEST, "workloads": MANIFEST["workloads"] + [third]}))
    (tmp_path / "benchmarks" / "traffic" / "third.json").write_text(
        json.dumps({"driver": "import_third"}))
    env = dict(os.environ, PYTHONPATH=bench.ROOT)  # the program, for the fixtures

    def pytest_there(*args):
        return subprocess.run(
            [sys.executable, "-m", "pytest", "benchmarks/tests/test_benchmark.py",
             "-q", "-p", "no:cacheprovider", "-p", "no:xdist", *args],
            cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)

    listed = pytest_there("--collect-only", "-k", "planted_fault")
    assert listed.returncode == 0, listed.stdout + listed.stderr
    ids = [ln.split("[", 1)[1].rstrip("]") for ln in listed.stdout.splitlines()
           if "test_planted_fault_makes_correct_false[" in ln]
    ours = [p.id for p in _fault_cases()]
    assert ids == ours + ["b4_import.third-no_faults_module"]
    ran = pytest_there("-k", "no_faults_module")
    last = ran.stdout.splitlines()[-1]
    assert ran.returncode == 1 and last.startswith("1 failed") \
        and "error" not in last, ran.stdout
    assert "benchmarks/tests/faults/import_third.py" in ran.stdout


def test_no_chip_means_no_result(capsys):
    # without --rehearsal the CPU backend is refused before any result
    with pytest.raises(bench.BenchError):
        bench.main(["--workload", "b4_import.fleet16", "--seed", "1",
                    "--seconds", "0.2"])
    assert not capsys.readouterr().out.strip().endswith("}")
    with pytest.raises(bench.BenchError):
        bench.main(["--workload", "no.such_cell", "--seed", "1",
                    "--seconds", "0.2"])


EXPECT = {"busy_s": 0.003527159, "window_s": 0.159400225}


def test_trace_reduction_on_the_recorded_trace():
    """``testdata/tiny_v5e.xplane.pb``: three calls of one jitted program
    on a v5e, a 50 ms host sleep after each (probe, PR 24)."""
    t = trace_reduce.reduce_trace(os.path.join(HERE, "testdata",
                                               "tiny_v5e.xplane.pb"))
    assert t["devices"] == 1 and len(t["launches"]) == 3
    assert t["busy_s"] == pytest.approx(EXPECT["busy_s"], rel=1e-6)
    assert t["window_s"] == pytest.approx(EXPECT["window_s"], rel=1e-6)
    gaps = trace_reduce.launch_gaps(t["launches"])
    assert len(gaps) == 2 and all(0.050 < g < 0.056 for g in gaps)
    assert [n for n, _s in t["idle_gaps"][:2]] == ["bench.host_gap"] * 2
    assert t["device_ops"][0][1] >= t["device_ops"][-1][1] > 0


def test_trace_reduction_arithmetic():
    ev = {"devices": {"/device:TPU:0": {
        "ops": [("%a = f32[] x()", 1.0, 2.0), ("%b = f32[] y()", 1.5, 3.0),
                ("%a = f32[] x()", 6.0, 7.0)],
        "modules": [("jit_f", 1.0, 3.0), ("jit_f", 6.0, 7.0)]}},
        "spans": [("bench.window", 0.0, 10.0), ("bench.call", 0.5, 3.5),
                  ("bench.decode", 3.5, 6.5)]}
    t = trace_reduce.reduce_events(ev)
    assert t["window_s"] == 10.0 and t["busy_s"] == 3.0
    assert t["device_ops"] == [["a", 2.0], ["b", 1.5]]
    assert sorted(map(tuple, t["idle_gaps"])) == [
        ("bench.call", 1.0), ("bench.decode", 3.0), ("outside bench spans", 3.0)]
    assert trace_reduce.launch_gaps(t["launches"]) == [3.0]
    empty = trace_reduce.reduce_events({"devices": {"/device:TPU:0": {
        "ops": [], "modules": []}}, "spans": [("bench.window", 0.0, 1.0)]})
    assert empty["busy_s"] is None  # nothing ran: no share of anything


def test_a_gap_is_named_by_the_innermost_span_over_its_middle():
    # two launches, 4 s of host work between them: the middle (t = 4.0)
    # lies in the program's fleet.decode, inside the benchmark's call; the
    # second's (t = 0.5) in no stage of the entry, so it reads the entry
    ev = {"devices": {"/device:TPU:0": {
        "ops": [("%a = f32[] x()", 1.0, 2.0), ("%a = f32[] x()", 6.0, 7.0)],
        "modules": [("jit_f", 1.0, 2.0), ("jit_f", 6.0, 7.0)]}},
        "spans": [("bench.window", 0.0, 7.0), ("bench.call", 0.0, 2.5),
                  ("fleet.merge_text_payloads", 0.1, 2.5), ("fleet.join", 2.1, 2.5),
                  ("bench.call", 2.5, 7.0), ("fleet.merge_text_payloads", 2.6, 7.0),
                  ("fleet.decode", 2.7, 4.5), ("native.explode", 2.8, 3.2),
                  ("fleet.pack", 4.5, 5.5)]}
    t = trace_reduce.reduce_events(ev)
    assert t["idle_gaps"] == [["fleet.decode", 4.0],
                              ["fleet.merge_text_payloads", 1.0]]
    assert t["busy_s"] == 2.0 and t["window_s"] == 7.0


def test_the_launching_threads_program_spans_are_read_from_a_trace(tmp_path):
    """A trace recorded here (host plane only): the program's spans of the
    thread that holds ``bench.*`` are kept, a worker thread's are not."""
    import threading

    import jax.profiler as P

    from loro_tpu.utils import tracing

    def worker():
        with tracing.span("packed.decode_one", doc=3):
            pass

    opts = P.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 1
    P.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with P.TraceAnnotation("bench.window"), P.TraceAnnotation("bench.call"):
            other = threading.Thread(target=worker)
            other.start()
            with tracing.span("fleet.merge_text_payloads", docs=1):
                with tracing.span("fleet.decode", doc=0), tracing.span("native.explode"):
                    other.join()
    finally:
        P.stop_trace()
    ev = trace_reduce.load_events(trace_reduce.find_xplane(str(tmp_path)))
    names = [n for n, _s, _e in ev["spans"]]
    assert names == ["bench.window", "bench.call", "fleet.merge_text_payloads",
                     "fleet.decode", "native.explode"]
    t = trace_reduce.reduce_events(ev)  # no device plane: nothing ran
    assert t["busy_s"] is None and t["window_s"] > 0


def test_byte_counts_from_shapes():
    assert bytes_model.import_bytes(1000, 22, 4) == 26000.0
    assert bytes_model.roofline_pct(819e9, 819e9, 4.0) == 25.0
    peaks = bench.load_json(HERE, "peaks.json")
    assert bytes_model.load_peak("TPU v5 lite", "hbm_bytes_per_s", peaks) == 819e9
    with pytest.raises(KeyError):
        bytes_model.load_peak("TPU v9 heavy", "hbm_bytes_per_s", peaks)


# ---------------------------------------------------------------------------
# the edit script and the plain reference
# ---------------------------------------------------------------------------

CONFIG = bench.load_json(HERE, "configs", "b4_import.json")  # the script is text's
TINY = {**CONFIG, **CONFIG["rehearsal"]}


@pytest.mark.parametrize("seed", [0, 7, 2147483659, 4294967311])
def test_script_meets_the_configurations_counts_exactly(seed):
    import script

    patches = script.routed_patches(seed, TINY, 1)
    assert sum(1 for _p, _pos, ch in patches if ch) == TINY["insert_patches"]
    assert sum(1 for _p, _pos, ch in patches if not ch) == TINY["delete_patches"]
    assert all(len(ch) <= 1 for _p, _pos, ch in patches)  # single characters
    quiet = patches[:TINY["sync_every_patches"]]
    assert all(ch for _p, _pos, ch in quiet)  # nothing deleted before the first exchange
    assert {p for p, _pos, _ch in patches} == set(range(TINY["peers_per_document"]))
    assert patches == script.routed_patches(seed, TINY, 1)  # the seed alone decides
    assert patches != script.routed_patches(seed, TINY, 2)


def _merged(edits, n_peers=2):
    """Replicas type ``edits`` (``(peer, pos, char)``, no exchange in
    between), then exchange: the text all of them read."""
    import fugue_reference

    m = fugue_reference.Merge(n_peers)
    for peer, pos, ch in edits:
        m.apply(peer, pos, ch)
    m.exchange()
    return "".join(m.ch[e] for e in m.visible())


def test_reference_orders_concurrent_runs_by_peer_without_interleaving():
    # both replicas type a word into the empty text: root children, by peer
    assert _merged([(1, 0, "x"), (1, 1, "y"), (0, 0, "a"), (0, 1, "b")]) == "abxy"


def test_reference_places_by_the_fugue_rule():
    import fugue_reference

    m = fugue_reference.Merge(2)
    for i, ch in enumerate("abc"):
        m.apply(0, i, ch)
    m.exchange()
    # after "a", which has the right child "b": the LEFT child of "b"
    m.apply(0, 1, "X")
    assert (m.parent[3], m.side[3]) == (1, 0)
    # replica 1 does not see X: after "a" it makes a left child of "b" too
    m.apply(1, 1, "Y")
    assert (m.parent[4], m.side[4]) == (1, 0)
    # after "c", which has no child: its RIGHT child; at 0: left of the first
    m.apply(1, 4, "Z")
    assert (m.parent[5], m.side[5]) == (2, 1)
    m.apply(0, 0, "S")
    assert (m.parent[6], m.side[6]) == (0, 0)
    m.apply(1, 2, "")  # replica 1 reads "aYbcZ": deletes "b"
    stale = m.views[0].text()
    m.exchange()
    assert stale == "SaXbc"
    assert "".join(m.ch[e] for e in m.visible()) == "SaXYcZ"  # siblings X, Y by peer
    assert m.chains() == 7  # nothing folds: two children, another peer, a left child
    m.apply(0, 1, "T")  # the next id of S's peer, its only child, on the right
    m.exchange()
    assert "".join(m.ch[e] for e in m.visible()) == "STaXYcZ" and m.chains() == 7


def test_reference_delete_of_one_character_by_two_replicas_deletes_one():
    edits = [(0, 0, "a"), (0, 1, "b"), (0, 2, "c")]
    import fugue_reference

    m = fugue_reference.Merge(2)
    for e in edits:
        m.apply(*e)
    m.exchange()
    m.apply(0, 1, "")
    m.apply(1, 1, "")
    m.exchange()
    assert "".join(m.ch[e] for e in m.visible()) == "ac"


def test_documents_off_the_sources_shape_fail_set_up(capsys, monkeypatch):
    real = bench.load_json

    def off(*parts):
        body = real(*parts)
        if parts[-1].endswith("b4_import.json"):
            body["rehearsal"]["chains_after_contraction"] = 1000
        return body

    monkeypatch.setattr(bench, "load_json", off)
    with pytest.raises(RuntimeError, match="the configuration states"):
        run_cell(capsys, "b4_import.fleet16")
