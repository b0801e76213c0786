"""DeviceSupervisor: every fleet/resident device call routes through it.

The rules it enforces (docs/RESILIENCE.md):

- **bounded in-flight budget** — the async launch queue never grows
  past ``drain_every`` launches before a sync drains it, so a deadline
  or a failure is never more than a few launches away from a point
  where the host knows what the device has finished;
- **cooperative deadlines** — checked BETWEEN launches only; a deadline
  expiry raises DeadlineExceeded at a launch boundary and never
  interrupts a compile or a transfer;
- **bounded retry with exponential backoff** — transient
  ``UNAVAILABLE``-class errors retry up to ``max_retries`` with
  ``backoff_base * 2**attempt`` sleeps (capped); anything else — or an
  exhausted budget — becomes a typed DeviceFailure the caller can
  degrade on.  Launches that donate buffers pass ``retry=False``
  (a failed donated launch may have consumed its inputs);
- **program faults propagate** — a compiler refusal (Mosaic, XLA
  ``INVALID_ARGUMENT``/``UNIMPLEMENTED``/``INTERNAL`` at compile time)
  or a ``RESOURCE_EXHAUSTED`` (the program does not fit the device's
  memory) says the PROGRAM is wrong for this chip, not that the chip
  is unwell: it is raised to the caller unchanged, never retried and
  never degraded around — a host engine that quietly answers instead
  would hide that the device path does not run.

Only device/runtime-layer errors (``jax.errors.JaxRuntimeError`` that
is not a program fault, OSError, transient ``UNAVAILABLE``-marked
errors, injected faults) are ever wrapped into DeviceFailure.
Host-side errors — poison payloads (CodecDecodeError / ValueError), bad
change lists, config errors like "capacity exceeded" — pass through
untouched: they must reach the per-doc isolation logic or the caller's
eyes, not the degradation logic.

All outcomes feed the obs registry (``resilience.*`` metrics) and the
``report()`` dict (chip_smoke.py and benchmarks/checks.py read it).
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Optional

import numpy as np

from ..analysis.lockwitness import named_lock
from ..errors import DeadlineExceeded, DeviceFailure, LoroError
from ..obs import flight
from ..obs import metrics as obs
from ..utils import tracing
from . import faultinject

faultinject.register_site(
    "launch", "DeviceSupervisor.launch: raise before the device call "
    "(transient UNAVAILABLE retries; anything else -> DeviceFailure)")
faultinject.register_site(
    "fetch", "DeviceSupervisor.fetch/drain: slow or failing host fetch")

# substrings that mark an error transient (retry-worthy): runtime/RPC
# errors of a device that is unwell but alive
_TRANSIENT_MARKERS = ("UNAVAILABLE", "DEADLINE_EXCEEDED", "ABORTED",
                      "connection reset", "temporarily")

# status codes / substrings of a JaxRuntimeError that say the PROGRAM is
# wrong for this chip: it does not fit (RESOURCE_EXHAUSTED) or the
# compiler refused it.  INTERNAL counts only with a compile-time marker:
# a runtime INTERNAL (a halted core) is the device's fault.
_PROGRAM_FAULT_CODES = ("RESOURCE_EXHAUSTED", "INVALID_ARGUMENT",
                        "UNIMPLEMENTED")
_COMPILE_MARKERS = ("compil", "lowering")


def is_program_fault(exc: BaseException) -> bool:
    """A runtime error that no retry and no healthy device would cure:
    out of device memory, or a compiler refusal.  These propagate
    unchanged through launch/guard (see the module docstring)."""
    import jax  # lazily: host-engine users of the package never load it

    if not isinstance(exc, jax.errors.JaxRuntimeError):
        return False
    msg = str(exc)
    if msg.startswith(_PROGRAM_FAULT_CODES) or "Mosaic" in msg:
        return True
    return msg.startswith("INTERNAL") and any(m in msg for m in _COMPILE_MARKERS)


def default_transient(exc: BaseException) -> bool:
    s = f"{type(exc).__name__}: {exc}"
    return any(m in s for m in _TRANSIENT_MARKERS)


def _is_device_error(exc: BaseException) -> bool:
    """Errors from the device/runtime layer — the only ones the
    supervisor may wrap into DeviceFailure.  Host-side errors (data
    errors, config errors like 'capacity exceeded ... pass
    auto_grow=True') pass through untouched so their guidance reaches
    the caller instead of being swallowed into silent degradation."""
    import jax

    return isinstance(exc, (OSError, ConnectionError, SystemError,
                            jax.errors.JaxRuntimeError))


class RetryPolicy:
    """Bounded retry with exponential backoff (no jitter: deterministic
    under fake clocks)."""

    def __init__(self, max_retries: int = 3, backoff_base: float = 0.25,
                 backoff_max: float = 8.0,
                 retryable: Callable[[BaseException], bool] = default_transient):
        self.max_retries = max_retries
        self.backoff_base = backoff_base
        self.backoff_max = backoff_max
        self.retryable = retryable

    def backoff(self, attempt: int) -> float:
        """Sleep before retry `attempt` (0-based)."""
        return min(self.backoff_base * (2.0 ** attempt), self.backoff_max)


class DeviceSupervisor:
    """Supervised execution of device launches and fetches.

    ``clock``/``sleep`` are injectable (tests use fake clocks; tier-1
    never wall-sleeps).  A supervisor is cheap enough to leave on every
    path: one lock + a couple of counters per launch.
    """

    def __init__(self, drain_every: int = 8, retry: Optional[RetryPolicy] = None,
                 deadline_s: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep):
        self.drain_every = max(1, int(drain_every))
        self.retry = retry if retry is not None else RetryPolicy()
        self.clock = clock
        self.sleep = sleep
        self._deadline = None if deadline_s is None else clock() + deadline_s
        self._lock = named_lock("supervisor.state")
        self._in_flight = 0
        # report counters (reset via reset_report)
        self._launches = 0
        self._retries = 0
        self._failures = 0
        self._degradations = 0
        self._deadline_aborts = 0
        self._drains = 0
        self._max_in_flight = 0

    # -- deadline ------------------------------------------------------
    def set_deadline(self, deadline_s: Optional[float]) -> None:
        """(Re)arm the cooperative deadline, `deadline_s` from now."""
        self._deadline = None if deadline_s is None else self.clock() + deadline_s

    def remaining(self) -> Optional[float]:
        return None if self._deadline is None else self._deadline - self.clock()

    def check_deadline(self, label: str = "") -> None:
        """Raise DeadlineExceeded if the budget is spent.  Called only
        BETWEEN launches — expiry never interrupts in-flight work."""
        r = self.remaining()
        if r is not None and r <= 0:
            with self._lock:
                self._deadline_aborts += 1
            obs.counter("resilience.deadline_aborts_total").inc(label=label or "-")
            raise DeadlineExceeded(
                f"cooperative deadline expired before launch {label!r} "
                f"(over by {-r:.1f}s); in-flight work was never signaled"
            )

    # -- launches ------------------------------------------------------
    def launch(self, thunk: Callable[[], object], label: str = "launch",
               retry: bool = True, drain: Optional[Callable[[], None]] = None):
        """Run one device launch (an async dispatch: jit call,
        device_put, donated scatter...).  Retries transient errors when
        ``retry`` (pure, non-donating thunks only), wraps terminal
        runtime errors into DeviceFailure, and fetch-drains the queue
        every ``drain_every`` launches via ``drain`` (or the next
        explicit ``fetch``/``drain`` call when None)."""
        self.check_deadline(label)
        attempts = 0
        while True:
            injected = True
            try:
                faultinject.check("launch", label=label)
                injected = False
                out = thunk()
                break
            except LoroError:
                raise
            except BaseException as e:  # noqa: BLE001 — classified below
                if is_program_fault(e):
                    raise  # wrong program for this chip: never retried, never degraded
                transient = self.retry.retryable(e)
                if not (injected or transient or _is_device_error(e)):
                    # host-side error (poison payload, bad change list,
                    # capacity config): not the device's fault — reach
                    # the isolation logic / the caller unchanged
                    raise
                attempts += 1
                if retry and transient and attempts <= self.retry.max_retries \
                        and (self.remaining() is None or self.remaining() > 0):
                    with self._lock:
                        self._retries += 1
                    obs.counter("resilience.retries_total").inc(label=label)
                    flight.record("sup.retry", label=label,
                                  attempt=attempts,
                                  error=f"{type(e).__name__}: {e}"[:160])
                    self.sleep(self.retry.backoff(attempts - 1))
                    continue
                with self._lock:
                    self._failures += 1
                obs.counter("resilience.launch_failures_total").inc(label=label)
                flight.record("sup.failure", label=label,
                              attempts=attempts,
                              error=f"{type(e).__name__}: {e}"[:160])
                raise DeviceFailure(
                    label, attempts, f"{type(e).__name__}: {e}"
                ) from e
        with self._lock:
            self._launches += 1
            self._in_flight += 1
            self._max_in_flight = max(self._max_in_flight, self._in_flight)
            depth = self._in_flight
            # NOT retained across calls: holding the caller's bound
            # drain method on the process-global supervisor would pin
            # the enclosing object (e.g. a whole resident batch) long
            # after the caller is gone
        obs.counter("resilience.launches_total").inc(label=label)
        obs.gauge("resilience.in_flight").set(depth)
        if depth >= self.drain_every:
            self.drain(drain if drain is not None else self._auto_drain(out),
                       label=label)
        return out

    def _auto_drain(self, result) -> Callable[[], None]:
        """Default drain: wait for the launch result (it was enqueued
        last, so everything before it has finished too)."""
        import jax

        return lambda: jax.block_until_ready(result)

    def guard(self, fn: Callable[[], object], label: str = "fetch"):
        """Run a device-touching host read (fetch / state export) and
        classify failures exactly like launch does — JAX dispatch is
        async, so a mid-merge device failure often surfaces at the SYNC
        point, not the launch; without this, sync-point errors would
        bypass every ``except DeviceFailure`` degradation handler.  No
        retry: the queue state behind a failed fetch is unknown."""
        injected = True
        try:
            faultinject.check("fetch", label=label)
            injected = False
            return fn()
        except LoroError:
            raise
        except BaseException as e:  # noqa: BLE001 — classified below
            if is_program_fault(e) or not (
                    injected or self.retry.retryable(e) or _is_device_error(e)):
                raise
            with self._lock:
                self._failures += 1
            obs.counter("resilience.launch_failures_total").inc(label=label)
            raise DeviceFailure(label, 1, f"{type(e).__name__}: {e}") from e

    def drain(self, drain_fn: Optional[Callable[[], None]] = None,
              label: str = "drain") -> None:
        """Synchronize: run the drain fetch and zero the in-flight
        count (with no ``drain_fn`` it only resets the counters — the
        caller already synced some other way).  The fetch is a host wait
        inside whatever the caller times, one launch in ``drain_every``:
        the span ``sup.drain`` names it, with the ``label`` of the launch
        that filled the queue."""
        fn = drain_fn
        if fn is not None:
            try:
                with tracing.span("sup.drain", label=label):
                    self.guard(fn, label="drain")
            except BaseException:
                # the queue state behind a failed drain is unknown, but
                # the depth counter must not keep climbing past the
                # budget while the caller degrades — reset it with the
                # failure in flight
                with self._lock:
                    self._in_flight = 0
                obs.gauge("resilience.in_flight").set(0)
                raise
        with self._lock:
            self._in_flight = 0
            self._drains += 1
        obs.counter("resilience.drains_total").inc()
        obs.gauge("resilience.in_flight").set(0)

    def fetch(self, value, label: str = "fetch"):
        """Supervised host fetch (np.asarray): the sync point of a
        merge.  Resets the in-flight count — a fetch drains the queue
        through it.  Device errors surfacing here become typed
        DeviceFailure (see guard)."""
        out = self.guard(lambda: np.asarray(value), label=label)
        with self._lock:
            self._in_flight = 0
        obs.gauge("resilience.in_flight").set(0)
        return out

    @property
    def in_flight(self) -> int:
        with self._lock:
            return self._in_flight

    @property
    def max_in_flight(self) -> int:
        with self._lock:
            return self._max_in_flight

    # -- degradation accounting ---------------------------------------
    def note_degradation(self, where: str) -> None:
        """Callers report a host-fallback degradation so the bench
        sidecar captures it.  The flight recorder logs the event and —
        when auto-dumping is armed (``LORO_FLIGHT_DIR``) — writes the
        black-box snapshot: the last N structured events BEFORE the
        degradation, which is exactly what the post-mortems never had
        (docs/OBSERVABILITY.md "Flight recorder")."""
        with self._lock:
            self._degradations += 1
        obs.counter("resilience.degradations_total").inc(where=where)
        flight.record("sup.degrade", where=where)
        flight.dump_on(f"degradation:{where}")

    # -- reporting -----------------------------------------------------
    def report(self) -> dict:
        """Compact outcome dict for the bench ``resilience`` sidecar."""
        with self._lock:
            return {
                "launches": self._launches,
                "retries": self._retries,
                "failures": self._failures,
                "degradations": self._degradations,
                "deadline_aborts": self._deadline_aborts,
                "drains": self._drains,
                "max_in_flight": self._max_in_flight,
                "drain_every": self.drain_every,
            }

    def reset_report(self) -> None:
        with self._lock:
            self._launches = self._retries = self._failures = 0
            self._degradations = self._deadline_aborts = self._drains = 0
            self._max_in_flight = self._in_flight = 0


# -- process-default supervisor ----------------------------------------
_default: Optional[DeviceSupervisor] = None
_default_lock = threading.Lock()


def get_supervisor() -> DeviceSupervisor:
    global _default
    with _default_lock:
        if _default is None:
            _default = DeviceSupervisor()
        return _default


def set_supervisor(sup: Optional[DeviceSupervisor]) -> None:
    """Install a process-wide supervisor (None restores a fresh
    default).  Tests install fake-clock instances."""
    global _default
    with _default_lock:
        _default = sup
