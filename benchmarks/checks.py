"""The checks every cell shares: counters that must not move, compile
events, the device's checksum of a text.  Copies of ``chip_smoke.py``'s
(``ZERO_COUNTERS``, ``check_clean``, ``CompileEvents``, ``text_checksum``),
which ran on the chip in PR 21."""
from __future__ import annotations

import time

import numpy as np

# each is a place where a host engine or a Python decoder answers instead
# of the device / native path, or where a launch was retried
ZERO_COUNTERS = (
    "fleet.degraded_merges_total",
    "fleet.host_fallback_total",
    "codec.native_build_failed_total",
    "resilience.retries_total",
    "resilience.launch_failures_total",
    "resilience.degradations_total",
    "server.degraded_rounds_total",
    "server.poison_docs_total",
    "readbatch.degraded_windows_total",
    "readbatch.window_errors_total",
)


def counters_moved() -> dict:
    """``{what: count}`` of every fallback / degradation / retry sign in
    this process so far; empty when the run was clean."""
    from loro_tpu import native
    from loro_tpu.obs import metrics as obs
    from loro_tpu.resilience import get_supervisor

    moved = {n: obs.counter(n).total() for n in ZERO_COUNTERS}
    rep = get_supervisor().report()
    moved.update({f"supervisor.{k}": rep[k]
                  for k in ("retries", "failures", "degradations")})
    if not native.available():
        moved["native.unavailable"] = 1
    return {k: v for k, v in moved.items() if v}


def text_checksum(text: str, pad_n: int) -> int:
    """Host twin of the device's weighted checksum of one document: what
    the packed step must report for ``text`` at row width ``pad_n``."""
    codes = np.zeros(pad_n, np.uint32)
    codes[: len(text)] = np.frombuffer(text.encode("utf-32-le"), np.uint32)
    wgt = (np.arange(pad_n, dtype=np.uint32) * np.uint32(2654435761)) % np.uint32(1 << 30)
    return int(((codes * wgt) % np.uint32(1 << 30)).sum(dtype=np.uint32))


class CompileEvents:
    """Counts, through ``jax.monitoring``, the executables this process
    asked its backend for (one event each, whether XLA compiled it or the
    persistent cache held it) and the persistent cache's hits and misses,
    with the host-clock time of the last such event."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        self.compiled = []  # the name of each executable, in order
        self.last_compile_at = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event: str, _secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiled.append(str(kw.get("fun_name", "?")))
            self.last_compile_at = time.perf_counter()

    def mark(self) -> tuple:
        return (len(self.compiled), self.hits, self.misses)

    def since(self, mark=(0, 0, 0)) -> dict:
        return {"backend_compiles": len(self.compiled) - mark[0],
                "persistent_cache_hits": self.hits - mark[1],
                "persistent_cache_misses": self.misses - mark[2]}

    def names_since(self, mark=(0, 0, 0)) -> list:
        return self.compiled[mark[0]:]
