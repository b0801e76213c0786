"""Profiler trace (``.xplane.pb``) -> the device's numbers.

The smallest reduction that gives them (read with nothing but
``jax.profiler.ProfileData``):

- busy: the union of the intervals in which an operation ran on a device
  (line ``XLA Ops`` of each ``/device:TPU:<n>`` plane), averaged over the
  device planes; idle share = 1 - busy / window;
- the top operations by summed duration, under the names the trace gives;
- launches: the events of line ``XLA Modules`` (one per executed program);
- the longest idle gaps, each named by the innermost span of the
  launching thread that covers the gap's middle: the benchmark's own
  ``TraceAnnotation`` (``bench.*``) or, inside it, the program's
  (``fleet.*``, ``packed.*``, ``native.*``: ``loro_tpu.utils.tracing``), or
  ``outside bench spans``.  The launching thread is the host plane's line
  that holds the ``bench.*`` spans; other threads' spans (the decode
  workers') are busy under every gap and would name none.

The window is the span of the ``bench.window`` annotation when there is
one, else first event start to last event end.  Device and host events
share the trace's time base to about a millisecond (probe, PR 24).
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE, SPAN_PREFIX, WINDOW_SPAN = "/host:CPU", "bench.", "bench.window"
PROGRAM_PREFIXES = ("fleet.", "packed.", "native.")
MIN_GAP_S = 1e-6  # back-to-back operations leave nanoseconds: not a gap


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals: list) -> list:
    """Sorted, merged ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def short_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = name.split(" = ", 1)[0].strip()
    return head.lstrip("%")[:80]


def load_events(path: str) -> dict:
    """``{"devices": {plane: {"ops": [...], "modules": [...]}},
    "spans": [(name, start, end)]}`` with times in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            rec = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    rec[key].append((ev.name, s, s + ev.duration_ns * 1e-9))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                mine = [(ev.name, ev.start_ns * 1e-9,
                         ev.start_ns * 1e-9 + ev.duration_ns * 1e-9)
                        for ev in line.events
                        if ev.name.startswith((SPAN_PREFIX, *PROGRAM_PREFIXES))]
                if any(n.startswith(SPAN_PREFIX) for n, _s, _e in mine):
                    spans += mine  # the launching thread's line
    return {"devices": devices, "spans": sorted(spans, key=lambda x: x[1])}


def reduce_events(events: dict, top: int = 10) -> dict:
    """The numbers of one traced window (see the module docstring).
    ``busy_s`` is None when no operation ran on a device."""
    devices, spans = events["devices"], events["spans"]
    window = next(((s, e) for n, s, e in spans if n == WINDOW_SPAN), None)
    if window is None:
        every = [x for d in devices.values() for x in d["ops"] + d["modules"]]
        every += spans
        if not every:
            return {"window_s": 0.0, "busy_s": None, "devices": 0,
                    "device_ops": [], "idle_gaps": [], "launches": [],
                    "n_device_ops": 0, "first_device_op_s": None,
                    "last_device_op_s": None}
        window = (min(x[1] for x in every), max(x[2] for x in every))
    lo, hi = window
    busy, per_op, gaps, launches = [], {}, [], []
    for name in sorted(devices):
        dev = devices[name]
        ivs = clip(union([(s, e) for _n, s, e in dev["ops"]]), lo, hi)
        busy.append(sum(e - s for s, e in ivs))
        for n, s, e in dev["ops"]:
            if e > lo and s < hi:
                k = short_name(n)
                per_op[k] = per_op.get(k, 0.0) + (min(e, hi) - max(s, lo))
        edges = [lo] + [t for iv in ivs for t in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] - edges[i] >= MIN_GAP_S]
        launches.append(sorted((n, s, e) for n, s, e in dev["modules"]
                               if e > lo and s < hi))
    inner = [x for x in spans if x[0] != WINDOW_SPAN]

    def gap_name(s: float, e: float) -> str:
        mid = 0.5 * (s + e)
        covering = [x for x in inner if x[1] <= mid <= x[2]]
        # the innermost covering span says most about what the host did
        return (min(covering, key=lambda x: x[2] - x[1])[0]
                if covering else "outside bench spans")

    gaps.sort(key=lambda g: g[0] - g[1])
    n_dev = len(devices)
    any_op = any(d["ops"] for d in devices.values())
    inside = [(s, e) for d in devices.values() for _n, s, e in d["ops"]
              if e > lo and s < hi]
    return {
        "window_s": hi - lo,
        # where in the window the device's record starts and ends: a
        # record that ends early was cut by the profiler's buffer
        "n_device_ops": len(inside),
        "first_device_op_s": min(s for s, _e in inside) - lo if inside else None,
        "last_device_op_s": max(e for _s, e in inside) - lo if inside else None,
        "busy_s": (sum(busy) / n_dev) if (n_dev and any_op) else None,
        "devices": n_dev,
        "device_ops": sorted(([k, v / max(n_dev, 1)] for k, v in per_op.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[gap_name(s, e), e - s] for s, e in gaps[:top]],
        "launches": launches[0] if launches else [],
    }


def launch_gaps(launches: list) -> list:
    """Seconds between the end of one launch of the program that took
    most device time and the start of its next one: what the device
    waits for between steps (decode, upload, fetch, text join)."""
    by_name = {}
    for n, s, e in launches:
        by_name.setdefault(n, []).append((s, e))
    if not by_name:
        return []
    main = max(by_name.values(), key=lambda ivs: sum(e - s for s, e in ivs))
    main.sort()
    return [main[i + 1][0] - main[i][1] for i in range(len(main) - 1)]


def reduce_trace(trace_dir_or_file: str, top: int = 10) -> dict:
    path = (trace_dir_or_file if trace_dir_or_file.endswith(".pb")
            else find_xplane(trace_dir_or_file))
    return reduce_events(load_events(path), top)
