"""loro_tpu.resilience: supervised device execution, fault injection,
and graceful host degradation for the fleet merge path.

Three pieces (docs/RESILIENCE.md has the full rules and rationale):

- ``supervisor``  — DeviceSupervisor: bounded in-flight launch budget
  with periodic drains, cooperative deadlines (checked BETWEEN
  launches, never interrupting a compile or a transfer), bounded retry
  with exponential backoff for transient ``UNAVAILABLE`` errors, typed
  DeviceFailure for terminal runtime errors, and program faults
  (compiler refusals, out of device memory) passed through unchanged.
- ``faultinject`` — env (``LORO_FAULT=...``) + programmatic fault
  hooks: launch exceptions, slow fetches, truncated codec bytes,
  per-doc poison payloads — every degradation path runs on the
  8-device CPU mesh in CI.
- ``hostpath``    — the host ``models/`` mirror that degraded resident
  epochs and Fleet merges re-run on (byte-identical by the
  differential-fuzz contract).

All outcomes report through the ``obs`` registry (``resilience.*``,
``faultinject.*``) and ``DeviceSupervisor.report()``.
"""
from __future__ import annotations

from ..errors import (
    DeadlineExceeded,
    DeviceFailure,
    ResilienceError,
)
from . import faultinject, hostpath
from .supervisor import (
    DeviceSupervisor,
    RetryPolicy,
    default_transient,
    get_supervisor,
    set_supervisor,
)

__all__ = [
    "DeadlineExceeded",
    "DeviceFailure",
    "DeviceSupervisor",
    "ResilienceError",
    "RetryPolicy",
    "default_transient",
    "faultinject",
    "get_supervisor",
    "hostpath",
    "set_supervisor",
]
