"""Rehearsal of chip_smoke.py on the CPU backend: its phase functions at
tiny size, in this process, and the ways it must fail loudly.  The full
run exists only on the chip (``python chip_smoke.py``); here the Pallas
rank runs in interpret mode, so nothing below says anything about the
device — only that the script's paths, arguments and checks are right."""
import hashlib
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from loro_tpu import native  # noqa: E402
from loro_tpu.bench_utils import PUBLISHED_PATCHES, TraceSource  # noqa: E402
from loro_tpu.errors import DeviceFailure, LoroError  # noqa: E402
from loro_tpu.obs import metrics as obs  # noqa: E402
from loro_tpu.parallel.mesh import make_mesh  # noqa: E402
from loro_tpu.resilience import DeviceSupervisor, set_supervisor  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_counters():
    """The smoke's checks read process-wide counters: start each test
    from zero, whatever ran before in this worker."""
    obs.reset()
    set_supervisor(None)
    yield
    obs.reset()
    set_supervisor(None)


@pytest.fixture
def one_device_mesh():
    return make_mesh([jax.devices()[0]])


@pytest.fixture(scope="module")
def workload():
    return chip_smoke.make_serve_workload(3, 48, 10, 4, 3)


@pytest.fixture(scope="module")
def variants():
    return [chip_smoke.replay_variant(3, 1500, v) for v in range(3)]


@pytest.fixture(scope="module")
def events():
    return chip_smoke.CompileEvents()  # listeners stay for the process


def interpreted_kernel(monkeypatch):
    """The rank rule answers as on the chip; off the chip the kernel it
    picks runs interpreted."""
    from loro_tpu.ops import pallas_rank

    monkeypatch.setattr(pallas_rank, "use_pallas_rank", lambda: True)


# ---------------------------------------------------------------------------
# the phases, tiny
# ---------------------------------------------------------------------------


def test_sync_phase(one_device_mesh):
    rec = chip_smoke.phase_sync(jax.devices()[0])
    assert rec["block_until_ready_ms"] > 0 and rec["scalar_fetch_ms"] > 0


def test_serve_phase(workload, one_device_mesh, tmp_path):
    rec = chip_smoke.phase_serve(workload, one_device_mesh, 48, 4096,
                                 str(tmp_path / "wal"), sample=8)
    assert rec["pushes"] == 12 and rec["device_set"] == [0]
    assert rec["read_plane"]["launches"] > 0
    assert rec["durable_epoch"] == rec["epoch"]
    assert rec["docs_compared"] >= len(workload["expected"]) + 8
    json.dumps(rec)  # a phase record is one JSON line


def test_serve_phase_notices_a_wrong_document(workload, one_device_mesh, tmp_path):
    di = next(iter(workload["expected"]))
    wrong = dict(workload, expected={**workload["expected"], di: "not this"})
    with pytest.raises(chip_smoke.SmokeFailure, match=f"document {di}"):
        chip_smoke.phase_serve(wrong, one_device_mesh, 48, 4096,
                               str(tmp_path / "wal"), sample=8)


def test_import_phase(variants, one_device_mesh, events, monkeypatch):
    interpreted_kernel(monkeypatch)
    public, flagship = chip_smoke.phase_import(
        variants, one_device_mesh, 4, 8, 4, events, pipeline_runs=2)
    assert public["padded_shape"] == [4, 2048] and public["launches"] == 1
    # the chain bucket, not the element bucket, sets the ring
    assert public["chains"] <= public["pad_c"] == 255 and public["ring_tokens"] == 512
    assert public["transport"] == "packed" and public["rank_spec"] == "pallas:ruling"
    assert flagship["rank_spec"] == "pallas:ruling"
    assert flagship["docs"] == 8 and flagship["launches"] == 2
    assert flagship["tpu_custom_call"] is False  # interpret mode: no kernel text
    # the inspected program is the launched one, and no timed run compiled
    assert flagship["second_jit"]["backend_compiles"] == 0
    assert len(flagship["pipeline_s"]) == 2 and len(flagship["step_ms_on_zero_buffer"]) == 5
    json.dumps([public, flagship])


def test_import_phase_fails_on_a_compile_inside_the_timed_pipeline(
        variants, one_device_mesh, events, monkeypatch):
    """A pipeline that builds an executable inside its timed window (as
    it did when it dispatched another jit entry than the one that was
    warmed) does not time the pipeline: the step must say so instead of
    printing the seconds."""
    from loro_tpu.ops import fugue_batch

    packed = fugue_batch.merge_text_payloads_packed

    def compiles_first(*args, **kw):
        jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(np.arange(7)))
        return packed(*args, **kw)

    interpreted_kernel(monkeypatch)
    monkeypatch.setattr(fugue_batch, "merge_text_payloads_packed", compiles_first)
    with pytest.raises(chip_smoke.SmokeFailure, match="compiled inside"):
        chip_smoke.phase_import(variants, one_device_mesh, 4, 8, 4, events)


def test_import_phase_fails_on_a_non_pallas_rank(variants, one_device_mesh, events):
    """On this backend the auto spec is the XLA rank: exactly the case
    the flagship step must refuse to pass."""
    with pytest.raises(chip_smoke.SmokeFailure, match="not pallas:ruling"):
        chip_smoke.phase_import(variants, one_device_mesh, 4, 8, 4, events)


def test_import_phase_notices_a_wrong_text(variants, one_device_mesh, events,
                                           monkeypatch):
    interpreted_kernel(monkeypatch)
    wrong = [dict(v) for v in variants]
    wrong[1]["text"] = wrong[1]["text"][::-1]
    with pytest.raises(chip_smoke.SmokeFailure, match="differs from the host"):
        chip_smoke.phase_import(wrong, one_device_mesh, 4, 8, 4, events)


def test_chips4_sharded_phase(workload):
    rec = chip_smoke.phase_chips4_sharded(workload, 48, 4096)
    assert rec["texts_equal"] and rec["shards"] == len(jax.devices())
    assert sorted(s[0] for s in rec["shard_device_sets"]) == list(
        range(len(jax.devices())))


def test_chips4_mesh_phase(variants, monkeypatch):
    interpreted_kernel(monkeypatch)
    rec = chip_smoke.phase_chips4_mesh(variants[:2], variants[1:])
    assert rec["fleet"]["equal_one_device"] and rec["batch"]["equal_one_device"]
    assert rec["batch"]["device_set"] == list(range(len(jax.devices())))
    assert len(rec["batch"]["shard_shapes"]) == len(jax.devices())


def test_ingest_rounds_hold_one_payload_per_document(workload):
    rounds = chip_smoke.ingest_rounds(workload, 48)
    assert all(len(r) == 48 for r in rounds)
    pushes = sum(len(s) for s in workload["script"])
    assert sum(u is not None for r in rounds[1:] for u in r) == pushes


# ---------------------------------------------------------------------------
# failure is loud
# ---------------------------------------------------------------------------


def test_check_clean_passes_on_a_quiet_process():
    assert chip_smoke.check_clean()["supervisor"]["retries"] == 0


@pytest.mark.parametrize("counter", chip_smoke.ZERO_COUNTERS)
def test_check_clean_fails_on_any_fallback_counter(counter):
    obs.counter(counter).inc(family="text")
    with pytest.raises(chip_smoke.SmokeFailure, match=counter):
        chip_smoke.check_clean()


def test_check_clean_fails_without_the_native_decoder(monkeypatch):
    monkeypatch.setattr(native, "available", lambda: False)
    with pytest.raises(chip_smoke.SmokeFailure, match="native decoder"):
        chip_smoke.check_clean()


def test_check_clean_fails_on_a_degraded_server():
    class Degraded:
        degraded = True

    with pytest.raises(chip_smoke.SmokeFailure, match="degraded"):
        chip_smoke.check_clean([Degraded()])


def test_check_clean_fails_on_a_supervisor_retry():
    sup = DeviceSupervisor(sleep=lambda s: None)
    set_supervisor(sup)
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("UNAVAILABLE: try again")
        return 1

    assert sup.launch(flaky) == 1
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_clean()


def test_the_script_never_says_ok_without_a_tpu():
    """``python chip_smoke.py`` where JAX has no accelerator: non-zero
    exit, ``"ok": false`` with the reason, no ``"ok": true`` anywhere.
    (The child is pinned to the CPU, so it never loads the TPU library.)"""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and "no TPU" in last["reason"]
    assert last["device"]["platform"] == "cpu"


# ---------------------------------------------------------------------------
# program faults propagate through the supervisor
# ---------------------------------------------------------------------------

PROGRAM_FAULTS = [
    "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of memory "
    "in memory space hbm. Used 17.2G of 15.75G hbm.",
    "INTERNAL: Mosaic failed to compile TPU kernel: not implemented: "
    "unsupported gather",
    "INVALID_ARGUMENT: during compilation: shapes do not match",
    "UNIMPLEMENTED: this op is not supported on TPU",
]


@pytest.mark.parametrize("msg", PROGRAM_FAULTS)
@pytest.mark.parametrize("entry", ["launch", "guard"])
def test_program_fault_propagates_unretried(msg, entry):
    sup = DeviceSupervisor(sleep=lambda s: pytest.fail("slept: it retried"))
    calls = []

    def thunk():
        calls.append(1)
        raise jax.errors.JaxRuntimeError(msg)

    with pytest.raises(jax.errors.JaxRuntimeError) as ei:
        getattr(sup, entry)(thunk)
    assert not isinstance(ei.value, LoroError) and len(calls) == 1
    rep = sup.report()
    assert rep["retries"] == 0 and rep["failures"] == 0


def test_runtime_device_errors_still_become_device_failures():
    """The degradation machinery stays: a runtime error of the device
    (not a refusal of the program) is wrapped, a transient one retried."""
    sup = DeviceSupervisor(sleep=lambda s: None)

    def halted():
        raise jax.errors.JaxRuntimeError("INTERNAL: Accelerator device halted")

    with pytest.raises(DeviceFailure):
        sup.launch(halted)
    calls = []

    def unavailable():
        calls.append(1)
        raise jax.errors.JaxRuntimeError("UNAVAILABLE: socket closed")

    with pytest.raises(DeviceFailure):
        sup.launch(unavailable)
    assert len(calls) == 1 + sup.retry.max_retries


# ---------------------------------------------------------------------------
# the data source
# ---------------------------------------------------------------------------


def test_synthetic_source_is_the_published_length_and_byte_stable():
    src = TraceSource.synthetic()
    patches = src.load()
    assert len(patches) == PUBLISHED_PATCHES == 259_778
    assert all(len(ins) + dels == 1 for _pos, dels, ins in patches)
    digest = hashlib.blake2b(repr(patches).encode(), digest_size=8).hexdigest()
    assert digest == "660b90b1180dc466"
    assert src.record() == {"trace": "synthetic", "seed": src.seed,
                            "patches": PUBLISHED_PATCHES}
    assert TraceSource.synthetic(seed=1).load(limit=500) != patches[:500]
    assert src.load(limit=500) == patches[:500]


# ---------------------------------------------------------------------------
# the native build is keyed on what it was built from
# ---------------------------------------------------------------------------


def test_native_binary_name_follows_source_and_flags(tmp_path, monkeypatch):
    src = tmp_path / "codec.cpp"
    src.write_text("int a;")
    monkeypatch.setattr(native, "_SRC", str(src))
    first = native._so_path()
    src.write_text("int b;")
    second = native._so_path()
    monkeypatch.setattr(native, "_CXX", native._CXX + ("-g",))
    third = native._so_path()
    assert len({first, second, third}) == 3
    assert os.path.basename(first).startswith("codec.") and first.endswith(".so")


def test_native_require_reports_a_failed_build(tmp_path, monkeypatch):
    src = tmp_path / "codec.cpp"
    src.write_text("this is not C++")
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_build_failed", False)
    monkeypatch.setattr(native, "_build_error", "")
    with pytest.raises(LoroError, match="native decoder unavailable"):
        native.require()
    assert not native.available()  # library users: a soft fallback
    assert obs.counter("codec.native_build_failed_total").total() == 1
