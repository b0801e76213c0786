"""Unit tests for loro_tpu.resilience: supervisor retry/backoff under a
fake clock (no wall-clock sleeps in tier-1), the bounded in-flight
drain budget, cooperative deadlines, and the fault-injection
harness."""

import pytest

from loro_tpu.errors import (
    CodecDecodeError,
    DeadlineExceeded,
    DeviceFailure,
)
from loro_tpu.resilience import (
    DeviceSupervisor,
    RetryPolicy,
    default_transient,
    faultinject,
)


class FakeClock:
    def __init__(self):
        self.t = 1000.0
        self.sleeps = []

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.sleeps.append(s)
        self.t += s


def make_sup(**kw):
    clk = FakeClock()
    kw.setdefault("clock", clk)
    kw.setdefault("sleep", clk.sleep)
    return DeviceSupervisor(**kw), clk


# ---------------------------------------------------------------------------
# retry / backoff
# ---------------------------------------------------------------------------


class TestRetry:
    def test_transient_retries_then_succeeds(self):
        sup, clk = make_sup(retry=RetryPolicy(max_retries=3, backoff_base=0.25))
        calls = []

        def thunk():
            calls.append(1)
            if len(calls) < 3:
                raise RuntimeError("UNAVAILABLE: TPU backend setup error")
            return "ok"

        assert sup.launch(thunk, label="t") == "ok"
        assert len(calls) == 3
        # exponential backoff under the fake clock: 0.25, 0.5
        assert clk.sleeps == [0.25, 0.5]
        assert sup.report()["retries"] == 2
        assert sup.report()["failures"] == 0

    def test_backoff_is_capped(self):
        p = RetryPolicy(max_retries=10, backoff_base=1.0, backoff_max=4.0)
        assert [p.backoff(i) for i in range(5)] == [1.0, 2.0, 4.0, 4.0, 4.0]

    def test_exhausted_budget_is_typed(self):
        sup, clk = make_sup(retry=RetryPolicy(max_retries=2, backoff_base=0.1))

        def thunk():
            raise RuntimeError("UNAVAILABLE: still down")

        with pytest.raises(DeviceFailure) as ei:
            sup.launch(thunk, label="flaky")
        assert ei.value.attempts == 3  # 1 try + 2 retries
        assert "flaky" in str(ei.value)
        assert len(clk.sleeps) == 2
        assert sup.report()["failures"] == 1

    def test_fatal_device_error_fails_fast(self):
        sup, clk = make_sup()

        def thunk():
            raise OSError("link dropped mid-upload")

        with pytest.raises(DeviceFailure) as ei:
            sup.launch(thunk)
        assert ei.value.attempts == 1
        assert clk.sleeps == []  # non-transient: no backoff burned

    def test_host_side_runtime_error_passes_through(self):
        """A config/logic error from OUR host code (e.g. 'capacity
        exceeded ... pass auto_grow=True') is not the device's fault:
        it must surface verbatim, never silently degrade."""
        sup, _ = make_sup()

        def thunk():
            raise RuntimeError("DeviceDocBatch capacity exceeded: pass auto_grow=True")

        with pytest.raises(RuntimeError, match="auto_grow"):
            sup.launch(thunk)
        assert sup.report()["failures"] == 0

    def test_data_errors_pass_through_untyped(self):
        """A poison payload is NOT a device failure: ValueError-class
        errors (incl. CodecDecodeError) must reach the per-doc
        isolation logic unchanged."""
        sup, _ = make_sup()
        with pytest.raises(CodecDecodeError):
            sup.launch(lambda: (_ for _ in ()).throw(CodecDecodeError("bad bytes")))
        with pytest.raises(KeyError):
            sup.launch(lambda: {}["missing"])
        assert sup.report()["failures"] == 0

    def test_default_transient_classifier(self):
        assert default_transient(RuntimeError("UNAVAILABLE: x"))
        assert default_transient(OSError("DEADLINE_EXCEEDED"))
        assert not default_transient(RuntimeError("segfault"))


# ---------------------------------------------------------------------------
# cooperative deadline
# ---------------------------------------------------------------------------


class TestDeadline:
    def test_deadline_checked_between_launches(self):
        sup, clk = make_sup(deadline_s=10.0)
        sup.launch(lambda: 1)
        clk.t += 11.0
        with pytest.raises(DeadlineExceeded):
            sup.launch(lambda: 2, label="late")
        assert sup.report()["deadline_aborts"] == 1

    def test_no_retry_past_deadline(self):
        sup, clk = make_sup(
            deadline_s=1.0, retry=RetryPolicy(max_retries=5, backoff_base=2.0)
        )

        def thunk():
            raise RuntimeError("UNAVAILABLE")

        # first backoff sleep (2s) crosses the deadline -> next attempt
        # is not taken; typed failure, no runaway retry loop
        with pytest.raises(DeviceFailure) as ei:
            sup.launch(thunk)
        assert ei.value.attempts <= 2


# ---------------------------------------------------------------------------
# in-flight drain budget
# ---------------------------------------------------------------------------


class TestDrainBudget:
    def test_1k_launch_stress_keeps_budget(self):
        """Acceptance gate: 1000 launches, in-flight depth never
        exceeds drain_every (the SIGTERM-post-mortem rule: a deep
        async queue must not exist)."""
        sup, _ = make_sup(drain_every=8)
        drains = []
        max_seen = 0
        for i in range(1000):
            sup.launch(lambda i=i: i, label="stress",
                       drain=lambda: drains.append(1))
            max_seen = max(max_seen, sup.in_flight)
        assert max_seen <= 8
        assert sup.max_in_flight <= 8
        assert len(drains) == 1000 // 8
        assert sup.report()["launches"] == 1000

    def test_device_error_at_fetch_is_typed(self):
        """Regression (review finding): JAX dispatch is async, so a
        device failure often surfaces at the SYNC point — fetch/drain
        must classify it into DeviceFailure like launch does, or every
        degradation handler is bypassed."""
        sup, _ = make_sup()

        class Exploding:
            def __array__(self, *a, **kw):
                raise OSError("link dropped at fetch")

        with pytest.raises(DeviceFailure):
            sup.fetch(Exploding())
        with pytest.raises(DeviceFailure):
            sup.drain(lambda: (_ for _ in ()).throw(OSError("dead")))
        # host-side errors at the sync point still pass through
        with pytest.raises(KeyError):
            sup.guard(lambda: {}["x"])

    @pytest.mark.parametrize("traced, drain_fn, want", [
        (True, True, ["sup.drain", "round"]),
        (True, False, ["round"]),  # nothing fetched: counters reset only
        (False, True, []),
    ])
    def test_the_drain_is_a_span_under_whatever_is_open(self, traced, drain_fn, want):
        """ISSUE 37: the fetch that drains the queue, one launch in
        ``drain_every``, is named — under the caller's open span, with the
        label of the launch that filled the queue — and only where a drain
        function runs and a record is kept."""
        from loro_tpu.utils import tracing

        sup, _ = make_sup(drain_every=2)
        drains = []
        fn = (lambda: drains.append(1)) if drain_fn else None
        tracing.clear()
        if traced:
            tracing.enable()
        try:
            with tracing.span("round"):
                sup.launch(lambda: 1, label="a", drain=fn)
                if fn is not None:
                    sup.launch(lambda: 2, label="b", drain=fn)
                else:
                    sup.drain()
            spans = tracing.events()
        finally:
            tracing.disable()
            tracing.clear()
        assert [e["name"] for e in spans] == want
        assert drains == ([1] if drain_fn else [])
        assert sup.in_flight == 0 and sup.report()["drains"] == 1
        if want[:1] == ["sup.drain"]:
            assert spans[0]["args"] == {"label": "b"}
            assert spans[0]["parent_id"] == spans[1]["span_id"]

    def test_fetch_resets_depth(self):
        sup, _ = make_sup(drain_every=100)
        for _ in range(5):
            sup.launch(lambda: 1)
        assert sup.in_flight == 5
        out = sup.fetch([1, 2, 3])
        assert list(out) == [1, 2, 3]
        assert sup.in_flight == 0


# ---------------------------------------------------------------------------
# fault injection
# ---------------------------------------------------------------------------


@pytest.mark.faultinject
class TestFaultInject:
    def test_raise_fault_fires_n_times(self):
        f = faultinject.inject("launch", times=2)
        sup, _ = make_sup(retry=RetryPolicy(max_retries=3, backoff_base=0.01))
        try:
            # injected default is transient UNAVAILABLE: two retries burn
            # the two armed shots, third attempt passes clean
            assert sup.launch(lambda: "ok") == "ok"
            assert f.fired == 2
            assert faultinject.fired("launch") == 2
        finally:
            faultinject.clear()
        assert faultinject.active() == {}

    def test_fatal_injected_launch(self):
        faultinject.inject(
            "launch", exc=RuntimeError("INTERNAL: injected"), times=1
        )
        sup, _ = make_sup()
        try:
            with pytest.raises(DeviceFailure):
                sup.launch(lambda: "never")
        finally:
            faultinject.clear()

    def test_slow_fetch_uses_injected_sleeper(self):
        slept = []
        faultinject.set_sleep(lambda s: slept.append(s))
        faultinject.inject("fetch", action="delay", delay_s=3.5, times=1)
        sup, _ = make_sup()
        try:
            out = sup.fetch([7])
            assert list(out) == [7]
            assert slept == [3.5]
        finally:
            faultinject.clear()
            faultinject.set_sleep(None)

    def test_mangle_truncate_and_bitflip(self):
        payload = bytes(range(32))
        faultinject.inject("decode", action="truncate", keep_bytes=10, times=1)
        try:
            assert faultinject.mangle("decode", payload) == payload[:10]
            assert faultinject.mangle("decode", payload) == payload  # exhausted
        finally:
            faultinject.clear()
        faultinject.inject("decode", action="bitflip", flip_at=3, times=1)
        try:
            got = faultinject.mangle("decode", payload)
            assert got[3] == payload[3] ^ 0x5A and got[:3] == payload[:3]
        finally:
            faultinject.clear()

    def test_poison_doc_scoping(self):
        faultinject.inject("poison_doc", action="truncate", keep_bytes=1,
                           docs=[1], times=None)
        try:
            assert faultinject.mangle("poison_doc", b"abcd", doc=0) == b"abcd"
            assert faultinject.mangle("poison_doc", b"abcd", doc=1) == b"a"
        finally:
            faultinject.clear()

    def test_env_spec_parsing(self):
        faultinject._install_env_entry("launch:raise:times=2:msg=UNAVAILABLE hi")
        faultinject._install_env_entry("decode:truncate=16")
        faultinject._install_env_entry("fetch:delay:s=0.5:docs=1+3")
        try:
            act = faultinject.active()
            assert act == {"launch": 1, "decode": 1, "fetch": 1}
            with pytest.raises(faultinject.InjectedFault, match="UNAVAILABLE hi"):
                faultinject.check("launch")
        finally:
            faultinject.clear()
